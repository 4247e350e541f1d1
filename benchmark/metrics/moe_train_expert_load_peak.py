"""How uneven the routers' load on the held experts is in training: the
busiest held expert's pairs over the mean pairs per held expert, per expert
layer and microbatch, mean over the window's steps
(``TrainStats.moe_pairs``)."""

from metrics import _hybrid_train


def read(view):
    pairs = _hybrid_train.window_pairs(view)
    if pairs is None:
        return None
    mean = pairs.mean(-1)
    if not (mean > 0).all():
        return None
    return float((pairs.max(-1) / mean).mean())
