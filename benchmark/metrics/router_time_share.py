"""Share of the device's busy time the expert layers spend outside their
grouped matmuls: the scope ``moe_router`` (scores, top-k, the sort of the
pairs) and what of ``moe_experts`` is not the Mosaic call (gathers, the
weighted scatter-add)."""

from lib import xplane
from metrics import _hybrid, _scopes


def read(view):
    kernels, _ = xplane.op_seconds(view["trace"],
                                   _hybrid.named("moe_experts"))
    return _scopes.share(view, lambda seconds: (
        seconds.get("moe_router", 0.0)
        + max(0.0, seconds.get("moe_experts", 0.0) - kernels)))
