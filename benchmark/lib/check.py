"""The comparisons that decide ``correct``: each number beside its limit.

A comparison returns ``{name: (value, limit)}``; a run is correct when every
value is a number no larger than its limit.
"""

import math
import statistics


def passed(compared: dict) -> bool:
    return all(isinstance(v, (int, float)) and math.isfinite(v) and v <= lim
               for v, lim in compared.values())


def leaf_gaps(got: dict, want: dict, leaves=None) -> list:
    """For each leaf the gap between the program's norm and the reference's
    (not the norm of a difference), measured against the reference's norm
    of that leaf or of the median leaf, whichever is larger: some leaves'
    norms are all but zero."""
    leaves = sorted(want) if leaves is None else leaves
    floor = statistics.median(want.values())
    return [abs(got[k] - want[k]) / max(want[k], floor) for k in leaves]


def moved_leaves(ref_grad_norms: dict) -> list:
    """Leaves the reference's first gradient moves: those whose gradient is
    nought to rounding (under a thousandth of the median leaf's) move under
    Adam by round-off alone, and are left out of the change."""
    floor = 1e-3 * statistics.median(ref_grad_norms.values())
    return sorted(k for k, g in ref_grad_norms.items() if g >= floor)


def training(seen: dict, ref: dict, limits: dict) -> dict:
    """Program against reference over the first steps: the first gradient's
    norm and the parameters' change, by the worst leaf and by the median
    leaf.  The worst leaf's gap is set by one small noisy leaf and swings
    from seed to seed; the median leaf's is steady, and is the one that
    tells bfloat16 from fp8 most clearly.  The steps' losses are read too
    (:func:`loss_gaps`) but not held to a limit: on random tokens no fault
    and no precision moves them far enough from a sound run's."""
    grad = leaf_gaps(seen["grad_norms"], ref["grad_norms"])
    change = leaf_gaps(seen["delta_norms"], ref["delta_norms"],
                       moved_leaves(ref["grad_norms"]))
    out = {}
    for name, gaps in (("grad_norm_gap", grad), ("change_norm_gap", change)):
        out[name] = (max(gaps), limits[name])
        out[name + "_median"] = (statistics.median(gaps),
                                 limits[name + "_median"])
    return out


def loss_gaps(seen: dict, ref: dict) -> list:
    """Each followed step's loss against the reference's, as a share."""
    return [abs(a - b) / abs(b)
            for a, b in zip(seen["losses"], ref["losses"])]


def logit_rms_gap(got, want) -> float:
    """Root mean square of the difference between the program's logits and
    the reference's over rows ``[n, vocab]``, each row centred (a shift of
    a whole row changes no probability), against the reference's own."""
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    got = got - got.mean(-1, keepdims=True)
    want = want - want.mean(-1, keepdims=True)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))
