"""Share of the device's busy time under the scope ``sample``: the argmax,
the draw's sort and passes over the vocabulary, the choice of the token."""

from metrics import _scopes


def read(view):
    return _scopes.share(view, lambda seconds: seconds.get("sample", 0.0))
