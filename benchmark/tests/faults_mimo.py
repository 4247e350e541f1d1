"""The faults the hybrid model's timed path can have, planted in the
program underneath the harness (as ``tests/faults.py`` plants those of the
GPT cells): each is a context manager.  ``test_mimo_cell.py`` sees
``correct`` come out false under each at a tiny size.  The same faults are
also arguments of the reference (``fault=``), which ``calibrate_mimo.py``
puts in the program's place on the chip to read each fault's numbers at the
cell's own size.
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


def _config_altered(alter):
    """Break the description the driver hands the engine."""
    from drivers import mimo_program

    real = mimo_program.transformer_config

    def broken(sz, dtype):
        cfg = real(sz, dtype)
        return dataclasses.replace(cfg, hybrid=alter(cfg.hybrid))

    return _patched(mimo_program, "transformer_config", broken)


def _kinds_altered(**changes):
    def alter(hybrid):
        kinds = tuple(
            dataclasses.replace(k, **{name: f(k) for name, f in
                                      changes.items()})
            if k.window is not None else k for k in hybrid.kinds)
        return dataclasses.replace(hybrid, kinds=kinds)
    return _config_altered(alter)


def window_too_wide():
    """The window one token too wide, in the kernels and the allocator."""
    return _kinds_altered(window=lambda k: k.window + 1)


def sink_left_out():
    """The window layers' sink logits left out of the softmax."""
    return _kinds_altered(sink=lambda k: False)


def value_scale_left_out():
    return _config_altered(
        lambda hybrid: dataclasses.replace(hybrid, value_scale=1.0))


def selection_bias_left_out():
    """The router chooses by the scores alone."""
    from apex_tpu.transformer import moe

    real = moe.route_topk
    return _patched(moe, "route_topk",
                    lambda logits, bias, top_k: real(logits, 0.0 * bias,
                                                     top_k))


def held_expert_left_out():
    """The last held expert's output left out."""
    from apex_tpu.transformer import moe

    real = moe.held_experts_ffn

    def broken(x, router, bias, gate_up, down, **kw):
        return real(x, router, bias, gate_up,
                    down.at[-1].set(0.0), **kw)

    return _patched(moe, "held_experts_ffn", broken)


FAULTS = {"window_too_wide": window_too_wide,
          "sink_left_out": sink_left_out,
          "value_scale_left_out": value_scale_left_out,
          "selection_bias_left_out": selection_bias_left_out,
          "held_expert_left_out": held_expert_left_out}
