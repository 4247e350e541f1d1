"""The expert layers' grouped matmuls' share of the device's busy time in
training: the own time of the kernels under the program's ``moe_experts``
(forward) and ``moe_experts_bwd`` (backward) scopes."""

from metrics import _common, _hybrid


def read(view):
    return _common.share_of_busy(view, _hybrid.named("moe_experts"))
