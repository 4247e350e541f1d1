"""The faults a timed path can have, planted underneath the harness: each is
a context manager that breaks the program where the drivers reach it.
``test_correct.py`` sees ``correct`` come out false under each at a tiny
size; ``calibrate.py`` reads a fault's numbers on the chip at a cell's size.
"""

import contextlib


@contextlib.contextmanager
def _patched(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _train_step_wrapped(wrap):
    """Break the step that ``build_gpt_3d`` hands the training driver."""
    from apex_tpu.transformer.testing import gpt_parallel_train as program

    real_build = program.build_gpt_3d

    def build(*args, **kwargs):
        init_fn, make_loss, make_train_step = real_build(*args, **kwargs)
        return init_fn, make_loss, lambda *a, **kw: wrap(
            make_train_step(*a, **kw))

    return _patched(program, "build_gpt_3d", build)


def state_unchanged():
    """A step that returns its state unchanged."""
    def wrap(step):
        def broken(params, state, tokens, sent):
            _, _, _, loss, stats = step(params, state, tokens, sent)
            return params, state, sent, loss, stats
        return broken
    return _train_step_wrapped(wrap)


def half_batch():
    """Half of the batch left out, the mean taken over the rest."""
    import jax.numpy as jnp

    def wrap(step):
        def broken(params, state, tokens, sent):
            half = tokens[: tokens.shape[0] // 2]
            return step(params, state, jnp.concatenate([half, half]), sent)
        return broken
    return _train_step_wrapped(wrap)


def exchange_left_out():
    """The exchange between chips left out: the sequence-parallel exit of
    every row-parallel linear keeps its own partial sum's chunk instead of
    reduce-scattering over the tensor-parallel ranks."""
    from apex_tpu.transformer.tensor_parallel import mappings

    def local_chunk_only(x, axis=mappings.TENSOR_AXIS):
        return mappings._split_local(x, axis, 0)

    return _patched(mappings, "reduce_scatter_to_sequence_parallel_region",
                    local_chunk_only)


def token_altered(every=7, vocab=250):
    """A token altered where it is produced: every ``every``-th token the
    engine emits is moved to the next id."""
    from apex_tpu.serving import engine as program

    real_emit = program.ServingEngine._emit
    count = [0]

    def emit(self, req, token, now):
        count[0] += 1
        if count[0] % every == 0:
            token = (token + 1) % vocab
        return real_emit(self, req, token, now)

    return _patched(program.ServingEngine, "_emit", emit)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "exchange_left_out": exchange_left_out,
          "token_altered": token_altered}
