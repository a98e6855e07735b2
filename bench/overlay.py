"""What the live cell's device readers share: the programs of the live
overlay's split stage-4 tail in a traced run's profile, and the shapes
the program's spans give them.

The overlay scores a micro-batch's candidates with the split tail: one
``decompress_maxsim_scores_batch`` dispatch over the base candidates
(inside the program's ``stage:live_base_exact`` span) and, where the
batch has delta candidates, one over those (``stage:live_delta``). Each
span carries its dispatch's shapes as stats (``B``, ``C``, ``Ld``,
``pd``, ``Lq``, ``dim``, ``K``, ``nbits``). A program without those
spans reads no roofline share.
"""

from __future__ import annotations

import functools
import pathlib

import harness
import peaks
import spans
import trace
import work

PROGRAM = "decompress_maxsim_scores_batch"
SHAPED = ("stage:live_base_exact", "stage:live_delta")
SHAPE_KEYS = ("B", "C", "Ld", "pd", "Lq", "dim", "K", "nbits")


def split_tail_work(B, C, Ld, pd, Lq, dim, K, nbits, **_) -> tuple:
    """→ (operations, bytes) of one split-tail dispatch: ``work.py``'s
    count of the tail's inputs and MaxSim, with the (B, C) float32 scores
    it writes in place of a top-k."""
    flops, nbytes = work.tail_work(B=B, C=C, Ld=Ld, pd=pd, Lq=Lq, dim=dim,
                                   K=K, k=0, nbits=nbits)
    return flops, nbytes + B * C * 4


@functools.lru_cache(maxsize=1)
def _modules(path: str, mtime_ns: int) -> list:
    from jax.profiler import ProfileData
    raw = trace.read(ProfileData.from_file(path))
    return [b - a for d in raw["devices"].values()
            for name, a, b in d["modules"] if PROGRAM in name]


def tail_device_s(rec: dict):
    """Device time of each execution of the split tail's program in the
    run's newest trace, s; None where there is none."""
    found = sorted((harness.WORK / rec["workload"]["name"] / "trace")
                   .glob("**/*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not found:
        return None
    path = pathlib.Path(found[-1])
    return _modules(str(path), path.stat().st_mtime_ns) or None


def tail_shapes(rec: dict) -> list:
    """The shapes of the split-tail dispatches whose spans start inside
    the device window of the run's trace."""
    return [{k: int(s[4][k]) for k in SHAPE_KEYS}
            for s in spans.inside(spans.load(rec))
            if s[1] in SHAPED and all(k in s[4] for k in SHAPE_KEYS)]


def tail_roofline(rec: dict):
    """Mean least time of the split-tail dispatches in the device window
    over the mean device time of an execution of its program, %; None
    where either is missing."""
    times, shapes = tail_device_s(rec), tail_shapes(rec)
    if not times or not shapes:
        return None
    peak = peaks.lookup(rec["device"]["kind"])
    least = sum(work.least_time(*split_tail_work(**s), peak)[0]
                for s in shapes) / len(shapes)
    return 100.0 * least / (sum(times) / len(times))
