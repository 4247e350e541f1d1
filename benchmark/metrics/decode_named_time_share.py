"""Share of the device's busy time under any of the program's scopes
(``apex/<name>`` round the primitive, or round the loop it runs in): the
measure of the tracing itself.  The rest is what XLA made with no
``op_name`` outside any loop, and the operations of programs that are not
registered."""

from metrics import _scopes


def read(view):
    return _scopes.share(view, lambda seconds: sum(
        s for scope, s in seconds.items()
        if scope not in (None, _scopes.AMBIGUOUS)))
