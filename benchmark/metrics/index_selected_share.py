"""The share of the rows the window's decode calls scanned that their
queries then attended (``dsa_rows_selected`` over ``dsa_rows_scanned`` of
the ``decode_plan`` span), in percent."""

from metrics import _sparse


def read(view):
    counted = _sparse.rows(view)
    if counted is None or not counted[0]:
        return None
    return 100.0 * counted[1] / counted[0]
