"""Collective operations' own time as a share of the busiest chip's busy
time: the part of the exchange that compute does not hide."""

from lib import xplane


def read(view):
    busiest = xplane.busiest(view["trace"])
    if view["chips"] < 2 or not busiest["collective_s"]:
        return None
    return 100.0 * busiest["collective_s"] / busiest["busy_s"]
