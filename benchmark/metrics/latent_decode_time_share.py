"""The latent layers' decode kernel's share of the device's busy time: the
own time of ``paged_decode_latent``."""

from metrics import _common, _hybrid


def read(view):
    return _common.share_of_busy(view, _hybrid.named("paged_decode_latent"))
