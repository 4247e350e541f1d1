"""The faults the sparse latent model's timed path can have, planted in the
program underneath the harness (as ``tests/faults_deepseek.py`` plants
those of the latent cell): each is a context manager.  ``test_glm_cell.py``
sees ``correct`` come out false under each at a tiny size, but for
``index_weights_unscaled``: the heads' weights' scale is one positive
constant, which no ranking of the index scores sees, so no output of the
program moves (the test says so).  The same faults are also arguments of
the reference (``fault=``, ``reference.glm_5.FAULTS``), which
``calibrate_glm.py`` puts in the program's place on the chip.
"""

import contextlib
import dataclasses


@contextlib.contextmanager
def _patched(obj, name, new):
    """``obj.name`` replaced by ``new`` for the block; a class's own entry
    (a ``staticmethod`` as it is) put back after."""
    old = vars(obj)[name] if isinstance(obj, type) else getattr(obj, name)
    setattr(obj, name, new)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _glm_config_altered(alter):
    from drivers import glm_program

    real = glm_program.transformer_config

    def broken(sz, dtype):
        cfg = real(sz, dtype)
        return dataclasses.replace(cfg, hybrid=alter(cfg.hybrid))

    return _patched(glm_program, "transformer_config", broken)


def _model():
    from apex_tpu.serving import model
    return model


def indexer_left_out():
    """Dense latent attention: every query attends its whole history (the
    selection keeps every position, at decode and at prefill)."""
    model = _model()
    stack = contextlib.ExitStack()
    for name in ("select_topk", "select_topk_mask"):
        real = getattr(model, name)
        stack.enter_context(_patched(
            model, name,
            lambda scores, k, real=real: real(scores, scores.shape[-1])))
    return stack


def _sparse_layers(self):
    return sum(1 for k in self.spec.layer_kinds if self.spec.kinds[k].sparse)


def selection_from_other_layer():
    """Each sparse layer but the first attends the positions the layer
    before it selected, at decode and at prefill."""
    model = _model()
    cls = model.HybridDecodeModel
    real, real_prefill = cls._sparse, cls._prefill_selection
    seen, masks = [], []

    def broken(self, kind, q, index, arena, keys, table, limits):
        if len(seen) == _sparse_layers(self):
            seen.clear()
        if not seen:
            out = real(self, kind, q, index, arena, keys, table, limits)
            seen.append(out[1])
            return out
        selected = seen[-1]
        live = selected >= 0
        rows = model.gather_rows(arena, table, model.jnp.maximum(selected, 0))
        out = model.sparse_latent_attention(
            q, rows, live, v_dim=kind.latent_rank,
            scale=self._latent_scale(kind))
        seen.append(selected)
        return out, selected

    def broken_prefill(self, kind, index, keys, table, limits):
        if len(masks) == _sparse_layers(self):
            masks.clear()
        mask = (real_prefill(self, kind, index, keys, table, limits)
                if not masks else masks[-1])
        masks.append(mask)
        return mask

    stack = contextlib.ExitStack()
    stack.enter_context(_patched(cls, "_sparse", broken))
    stack.enter_context(_patched(cls, "_prefill_selection", broken_prefill))
    return stack


def index_rope_left_out():
    model = _model()
    return _patched(model.HybridDecodeModel, "_index_rope",
                    staticmethod(lambda x, positions, r, kind: x))


def index_norm_left_out():
    model = _model()
    return _patched(model.HybridDecodeModel, "_index_norm",
                    staticmethod(lambda k, lp, kind: k))


def index_weights_unscaled():
    model = _model()
    return _patched(model, "index_weights_scale", lambda heads, dim: 1.0)


def routing_unnormalised():
    return _glm_config_altered(lambda hybrid: dataclasses.replace(
        hybrid, experts=dataclasses.replace(hybrid.experts,
                                            normalize=False)))


def index_keys_fp8():
    """The index keys rounded to fp8 (e4m3, per-tensor scaled) before they
    are cached and scored: a precision below the configuration's."""
    model = _model()
    real = model.HybridDecodeModel._index_rows

    def broken(self, *args):
        import jax

        q, w, k = real(self, *args)
        f = k.astype(model.jnp.float32)
        amax = model.jnp.maximum(model.jnp.max(model.jnp.abs(f)), 1e-30)
        k = (jax.lax.reduce_precision(f * (240.0 / amax), 4, 3)
             * (amax / 240.0)).astype(k.dtype)
        return q, w, k

    return _patched(model.HybridDecodeModel, "_index_rows", broken)


FAULTS = {"indexer_left_out": indexer_left_out,
          "selection_from_other_layer": selection_from_other_layer,
          "index_rope_left_out": index_rope_left_out,
          "index_norm_left_out": index_norm_left_out,
          "index_weights_unscaled": index_weights_unscaled,
          "routing_unnormalised": routing_unnormalised,
          "index_keys_fp8": index_keys_fp8}
# what no output of the program shows (module docstring)
INVISIBLE = ("index_weights_unscaled",)
