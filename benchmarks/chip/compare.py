"""The comparison that decides ``correct`` for the RTL farm cells.

Every result that the measured window's launches returned is compared,
stimulus by stimulus, with the plain reference (``reference.simulate``)
run on the same design and the same stimuli: the cycle at which the
stimulus stopped, the set of exception ids it raised, and the value of
every named state register. The comparison is exact, so every limit is 0.

Two numbers are compared, each against its limit:

``differ``
    launch results that are missing, belong to another stimulus, or differ
    from the reference in the stop cycle, the exceptions or any register.
``unfinished``
    launch results that did not stop with FINISH alone at the bench's
    finish cycle. The designs check themselves against golden values that
    their builder computes in plain Python, so this holds the stimulus
    planes to the design's own test as well.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence

FINISH = 1          # the designs' exception id for a clean finish

LIMITS: Dict[str, int] = {"differ": 0, "unfinished": 0}


@dataclass(frozen=True)
class Answer:
    """One stimulus's result in the shape the comparison reads."""

    batch_index: int
    cycles: int
    exception_ids: frozenset
    registers: Dict[str, int]


def answers(outcome) -> List[Answer]:
    """A reference outcome as per-stimulus answers: how the control is put
    in the program's place."""
    names = sorted(outcome.registers)
    cols = [outcome.registers[nm].tolist() for nm in names]
    return [Answer(b, int(outcome.cycles[b]), outcome.exceptions[b],
                   dict(zip(names, (col[b] for col in cols))))
            for b in range(outcome.batch)]


@dataclass
class Verdict:
    numbers: Dict[str, int]          # name -> value, in LIMITS' order
    attempted: int                   # launch results that were due
    failed: int                      # of those, results that broke a limit

    @property
    def correct(self) -> bool:
        return all(self.numbers[k] <= lim for k, lim in LIMITS.items())

    def checks(self) -> Dict[str, Dict[str, int]]:
        return {k: {"value": self.numbers[k], "limit": LIMITS[k]}
                for k in LIMITS}


def judge(launches: Sequence[Sequence], ref, finish_cycle: int) -> Verdict:
    """Compare each launch's per-stimulus results with the reference.

    ``launches`` holds one result list per launch; a result has
    ``batch_index``, ``cycles``, ``exception_ids`` and ``registers``."""
    B = ref.batch
    names = sorted(ref.registers)
    want = [{nm: v for nm, v in zip(names, vals)}
            for vals in zip(*(ref.registers[nm].tolist() for nm in names))]
    cycles = ref.cycles.tolist()
    differ = unfinished = failed = 0
    for results in launches:
        by_index: List = [None] * B
        for r in results:
            if 0 <= r.batch_index < B and by_index[r.batch_index] is None:
                by_index[r.batch_index] = r
        for b, r in enumerate(by_index):
            bad_ref = (r is None or r.cycles != cycles[b]
                       or r.exception_ids != ref.exceptions[b]
                       or r.registers != want[b])
            bad_fin = (r is None or r.cycles != finish_cycle
                       or r.exception_ids != {FINISH})
            differ += bad_ref
            unfinished += bad_fin
            failed += bad_ref or bad_fin
        # results beyond the batch, or a second result for one stimulus
        extra = len(results) - sum(r is not None for r in by_index)
        differ += extra
        failed += extra
    return Verdict({"differ": differ, "unfinished": unfinished},
                   attempted=B * len(launches), failed=failed)
