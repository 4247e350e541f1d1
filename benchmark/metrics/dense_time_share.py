"""Share of the device's busy time in the weight-streaming matmuls XLA
compiles: the scopes ``embed``, ``attn_proj`` (with ``mla_absorb_q`` and
``mla_expand_o`` inside it), ``dense_ffn``, ``moe_shared``, ``lm_head``."""

from metrics import _scopes


def read(view):
    return _scopes.share(view, lambda seconds: sum(
        seconds.get(scope, 0.0) for scope in _scopes.DENSE))
