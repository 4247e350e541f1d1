"""What the cache groups' decode kernel is handed (ISSUE 36): block tables
whose columns outside a slot's live pages hold what no arena indexes, and
the step plans ``_decode_flat`` passes its ``pallas_call``.  Shared by
``test_serving_latent.py`` and ``test_serving_hybrid.py``."""

import numpy as np

from apex_tpu.serving import paged_attention as pa


def spoiled(tables, lengths, block, n_blocks, window=None):
    """``tables`` with every column outside a slot's live pages holding -1
    or an id at or above ``n_blocks``, in turn: the columns past the
    length and, with a ``window``, those wholly behind it (handed back)."""
    tables = np.array(tables)
    cols = np.arange(tables.shape[1])
    for i, length in enumerate(np.asarray(lengths)):
        first = 0 if window is None else max(int(length) - window, 0) // block
        dead = (cols >= -(-int(length) // block)) | (cols < first)
        tables[i, dead] = np.where(cols[dead] % 2, -1, n_blocks + cols[dead])
    return tables


def plans_handed_to_the_kernel(monkeypatch):
    """The ``plan`` operand of every ``pallas_call`` made from now on, as
    the decode kernel receives it (the calls must be made outside ``jit``)."""
    plans, real = [], pa.pl.pallas_call

    def spy(*args, **kwargs):
        call = real(*args, **kwargs)

        def run(*operands):
            plans.append(np.asarray(operands[1]))
            return call(*operands)
        return run

    monkeypatch.setattr(pa.pl, "pallas_call", spy)
    return plans


def assert_in_arena(plans, n_blocks):
    assert plans
    for plan in plans:
        assert plan.min() >= 0 and plan.max() < n_blocks
