"""How uneven the router's load on the held experts is: the most pairs any
held expert of any layer took in a decode call over the mean pairs per held
expert, mean over the window's decode calls."""

from metrics import _hybrid


def read(view):
    sz = view["observed"]["sizes"]
    entries = sum(sz["experts"]) * sz["held"][1]
    ratios = [t["moe_peak_pairs"] * entries / t["moe_pairs"]
              for t in _hybrid.phase_fields(view, "decode_fetch", "moe_pairs",
                                            "moe_peak_pairs")
              if t["moe_pairs"]]
    return sum(ratios) / len(ratios) if ratios else None
