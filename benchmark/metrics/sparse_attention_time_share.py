"""The sparse layers' attention's share of the device's busy time: the own
time of the index scan (``paged_index_*`` and the glue in ``dsa_index``),
the selection (``dsa_select``) and the sparse attention
(``sparse_latent``)."""

from metrics import _scopes

SCOPES = ("dsa_index", "paged_index_decode", "paged_index_prefill",
          "dsa_select", "sparse_latent")


def read(view):
    seconds = _scopes.by_scope(view)
    if seconds is None or not any(seconds.get(s) for s in SCOPES):
        return None
    return _scopes.share(view, lambda found: sum(
        found.get(s, 0.0) for s in SCOPES))
