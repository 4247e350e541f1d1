"""The faults the latent model's timed path can have, planted in the
program underneath the harness (as ``tests/faults_mimo.py`` plants those of
the hybrid cell): each is a context manager.  ``test_deepseek_cell.py`` sees
``correct`` come out false under each at a tiny size.  The same faults are
also arguments of the reference (``fault=``), which ``calibrate_deepseek.py``
puts in the program's place on the chip to read each fault's numbers at the
cell's own size.
"""

import dataclasses

from faults_mimo import _patched


def _config_altered(alter):
    """Break the description the driver hands the engine."""
    from drivers import deepseek_program

    real = deepseek_program.transformer_config

    def broken(sz, dtype):
        cfg = real(sz, dtype)
        return dataclasses.replace(cfg, hybrid=alter(cfg.hybrid))

    return _patched(deepseek_program, "transformer_config", broken)


def _experts_altered(**changes):
    return _config_altered(lambda hybrid: dataclasses.replace(
        hybrid, experts=dataclasses.replace(hybrid.experts, **changes)))


def _kernels_wrapped(wrap):
    """Every latent kernel the model calls, fused or not, behind ``wrap``."""
    import contextlib

    from apex_tpu.serving import model

    names = ("paged_decode_latent", "paged_decode_latent_unfused",
             "paged_prefill_latent", "paged_prefill_latent_unfused")

    @contextlib.contextmanager
    def all_of_them():
        with contextlib.ExitStack() as stack:
            for name in names:
                stack.enter_context(_patched(
                    model, name, wrap(getattr(model, name))))
            yield

    return all_of_them()


def rope_term_left_out():
    """The scores drop ``q_rope . k_rope``: the queries' rotary lanes are
    nought when they reach the kernel."""
    def wrap(real):
        def broken(q, arena, *args, v_dim, **kw):
            return real(q.at[..., v_dim:].set(0), arena, *args, v_dim=v_dim,
                        **kw)
        return broken
    return _kernels_wrapped(wrap)


def yarn_scale_left_out():
    """The softmax scale without YaRN's ``m ** 2``."""
    def alter(hybrid):
        kind = hybrid.kinds[0]
        return dataclasses.replace(hybrid, kinds=(dataclasses.replace(
            kind, softmax_scale=kind.k_dim ** -0.5),))
    return _config_altered(alter)


def latent_norm_left_out():
    """The cached latent is not normed (nor is the one the layer's own
    query reads: they are the same rows)."""
    from apex_tpu.serving import model

    real = model.HybridDecodeModel._norm

    def broken(self, x, weight):
        kind = self.spec.kinds[0]
        if kind.latent and x.shape[-1] == kind.latent_rank:
            return x
        return real(self, x, weight)

    return _patched(model.HybridDecodeModel, "_norm", broken)


def group_limit_left_out():
    """The router takes its top experts among all of them."""
    return _experts_altered(n_groups=1, topk_groups=1)


def route_scale_left_out():
    return _experts_altered(route_scale=1.0)


def shared_left_out():
    """The shared experts' output left out."""
    from apex_tpu.transformer import moe

    real = moe.held_experts_ffn

    def broken(x, router, bias, gate_up, down, shared=None, **kw):
        return real(x, router, bias, gate_up, down, **kw)

    return _patched(moe, "held_experts_ffn", broken)


def values_from_whole_row(rotary: int = 8):
    """The kernel takes its values from the whole cached row, and the
    expansion then reads them ``rotary`` lanes late (the tiny preset's key
    is 8 lanes): they span the key's lanes."""
    def wrap(real):
        def broken(q, arena, *args, v_dim, **kw):
            out = real(q, arena, *args, v_dim=arena.shape[-1], **kw)
            return out[..., rotary:rotary + v_dim]
        return broken
    return _kernels_wrapped(wrap)


FAULTS = {"rope_term_left_out": rope_term_left_out,
          "yarn_scale_left_out": yarn_scale_left_out,
          "latent_norm_left_out": latent_norm_left_out,
          "group_limit_left_out": group_limit_left_out,
          "route_scale_left_out": route_scale_left_out,
          "shared_left_out": shared_left_out,
          "values_from_whole_row": values_from_whole_row}
