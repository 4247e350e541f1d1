"""The faults the hybrid trainer's timed path can have, planted in the
program underneath the harness (as ``faults_mimo.py`` plants the serving
path's): each is a context manager.  ``test_trinity_cell.py`` sees
``correct`` come out false under each at a tiny size on the CPU, and
``calibrate_trinity.py`` reads each fault's numbers on the chip at the
cell's own size.

Most faults are the description with one field changed.  The parameters
stay the sound description's (the weights the reference makes have every
leaf), so a fault's step simply never reads the leaf it leaves out: the
trainer is built twice, the sound one gives ``init_fn`` and the altered one
the step.
"""

import contextlib
import dataclasses


@contextlib.contextmanager
def _patched(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _hybrid_altered(alter):
    """The step computes ``alter(hybrid)``; the parameter tree is the sound
    description's."""
    from apex_tpu.transformer.testing import hybrid_train

    real = hybrid_train.build_hybrid_train

    def broken(cfg, **kw):
        init_fn, _, _ = real(cfg, **kw)
        _, make_loss_fn, make_train_step = real(
            dataclasses.replace(cfg, hybrid=alter(cfg.hybrid)), **kw)
        return init_fn, make_loss_fn, make_train_step

    return _patched(hybrid_train, "build_hybrid_train", broken)


def _kinds_altered(which, **changes):
    def alter(hybrid):
        kinds = tuple(dataclasses.replace(k, **{
            name: f(k) for name, f in changes.items()}) if which(k) else k
            for k in hybrid.kinds)
        return dataclasses.replace(hybrid, kinds=kinds)
    return _hybrid_altered(alter)


def _experts_altered(**changes):
    return _hybrid_altered(lambda hybrid: dataclasses.replace(
        hybrid, experts=dataclasses.replace(hybrid.experts, **changes)))


def window_too_wide():
    """The sliding layers' window one token too wide."""
    return _kinds_altered(lambda k: k.window is not None,
                          window=lambda k: k.window + 1)


def rotary_on_full_layer():
    """The full-attention layers rotate q and k as the sliding ones do."""
    return _kinds_altered(lambda k: k.window is None,
                          rotary_dim=lambda k: k.k_dim)


def gate_left_out():
    return _kinds_altered(lambda k: True, gate=lambda k: False)


def qk_norm_left_out():
    return _kinds_altered(lambda k: True, qk_norm=lambda k: False)


def post_norm_left_out():
    """The sublayers' outputs are added as they are (pre-norm blocks)."""
    return _hybrid_altered(
        lambda hybrid: dataclasses.replace(hybrid, sandwich_norm=False))


def shared_expert_left_out():
    return _experts_altered(shared_experts=0)


def route_scale_left_out():
    return _experts_altered(route_scale=1.0)


def last_held_expert_left_out():
    """The last held expert's output left out."""
    from apex_tpu.transformer import moe

    real = moe.held_experts_ffn

    def broken(x, router, bias, gate_up, down, **kw):
        return real(x, router, bias, gate_up, down.at[-1].set(0.0), **kw)

    return _patched(moe, "held_experts_ffn", broken)


def microbatch_left_out():
    """The step takes its mean over the first microbatch alone (the second
    is the first again)."""
    import jax.numpy as jnp

    from apex_tpu.transformer.testing import hybrid_train

    real = hybrid_train.build_hybrid_train

    def broken(cfg, **kw):
        init_fn, make_loss_fn, make_train_step = real(cfg, **kw)
        m = kw["num_microbatches"]

        def make_broken_step(*args, **step_kw):
            step = make_train_step(*args, **step_kw)

            def broken_step(params, state, tokens, *rest):
                first = tokens[: tokens.shape[0] // m]
                return step(params, state, jnp.concatenate([first] * m),
                            *rest)

            return broken_step

        return init_fn, make_loss_fn, make_broken_step

    return _patched(hybrid_train, "build_hybrid_train", broken)


FAULTS = {"window_too_wide": window_too_wide,
          "rotary_on_full_layer": rotary_on_full_layer,
          "gate_left_out": gate_left_out,
          "qk_norm_left_out": qk_norm_left_out,
          "post_norm_left_out": post_norm_left_out,
          "shared_expert_left_out": shared_expert_left_out,
          "route_scale_left_out": route_scale_left_out,
          "last_held_expert_left_out": last_held_expert_left_out,
          "microbatch_left_out": microbatch_left_out}
