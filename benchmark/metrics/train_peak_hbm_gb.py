"""Peak bytes in use on the fullest chip, in GB (1e9 bytes)."""


def read(view):
    peak = max(view["observed"]["memory"]["peak_bytes"])
    return peak / 1e9 if peak else None
