"""In-graph sampling (``apex_tpu.serving.sampling``): the drawn token is
what the plain two-sort, gather-and-scatter form below returns, greedy
rows are the exact argmax, an all-greedy batch pays no sort, and the
drawn branch orders each row once and moves nothing through a gather or
a scatter over the vocabulary."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.serving.sampling import sample_tokens

_NEG = -1e30
B = 64
VOCABS = (777, 1000, 19072)      # 777: no multiple of 128
TOP_KS = (0, 1, 40, "V")
TOP_PS = (1.0, 0.95, 0.5, 0.1)
TEMPERATURES = (0.0, 0.8, 0.3, 0.0, 1.7, 1.0, 0.0, 0.05)


# -- the plain reference: sort, mask, sort again, gather, scatter ------------

def _reference_one(logits, temperature, top_k, top_p, seed, step):
    vocab = logits.shape[0]
    x = logits / jnp.maximum(temperature, 1e-6)
    sorted_desc = jnp.sort(x)[::-1]
    kth = sorted_desc[jnp.clip(top_k - 1, 0, vocab - 1)]
    x = jnp.where((top_k > 0) & (x < kth), _NEG, x)
    probs = jax.nn.softmax(x)
    order = jnp.argsort(-x)
    csum = jnp.cumsum(probs[order])
    keep_sorted = (csum - probs[order]) < top_p
    keep = jnp.zeros_like(keep_sorted).at[order].set(keep_sorted)
    x = jnp.where(keep, x, _NEG)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    return jax.random.categorical(key, x).astype(jnp.int32)


@jax.jit
def _reference(logits, temperature, top_k, top_p, seeds, steps):
    logits = logits.astype(jnp.float32)
    sampled = jax.vmap(_reference_one)(
        logits, temperature.astype(jnp.float32), top_k.astype(jnp.int32),
        top_p.astype(jnp.float32), seeds.astype(jnp.uint32),
        steps.astype(jnp.int32))
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


_sample = jax.jit(sample_tokens)


# -- batteries ---------------------------------------------------------------

def _kept_edge(row, temperature, top_k, top_p):
    """(k-th largest logit, the logit on the nucleus' edge) of one row, by
    numpy: the values that ties are planted on."""
    x = np.sort(row.astype(np.float64) / max(temperature, 1e-6))[::-1]
    k = len(x) if top_k <= 0 else min(top_k, len(x))
    head = x[:k]
    p = np.exp(head - head[0])
    p /= p.sum()
    kept = int(np.sum(np.cumsum(p) - p < top_p))
    order = np.sort(row)[::-1]
    return order[k - 1], order[max(kept, 1) - 1]


def _battery(vocab, top_k, top_p, salt):
    """64 rows: temperatures mixed with greedy rows; the first half's logits
    are distinct but for ties planted on the k-th value and on the nucleus'
    edge, the second half's are rounded to halves so that ties sit on
    every cut."""
    rng = np.random.default_rng([vocab, top_k, int(top_p * 100), salt])
    logits = rng.normal(0.0, 2.5, (B, vocab)).astype(np.float32)
    logits[B // 2:] = np.round(logits[B // 2:] * 2.0) / 2.0
    temperature = np.resize(np.asarray(TEMPERATURES, np.float32), B)
    temperature = temperature[rng.permutation(B)]
    for r in range(B // 2):
        kth, edge = _kept_edge(logits[r], float(temperature[r]), top_k, top_p)
        at = rng.choice(vocab, 6, replace=False)
        logits[r, at[:3]] = kth
        logits[r, at[3:]] = edge
    return (jnp.asarray(logits), jnp.asarray(temperature),
            jnp.full((B,), top_k, jnp.int32),
            jnp.full((B,), top_p, jnp.float32),
            jnp.asarray(rng.integers(0, 2 ** 32, B, dtype=np.uint32)),
            jnp.asarray(rng.integers(0, 4096, B).astype(np.int32)))


@pytest.mark.parametrize("top_p", TOP_PS)
@pytest.mark.parametrize("top_k", TOP_KS)
@pytest.mark.parametrize("vocab", VOCABS)
def test_drawn_tokens_are_the_references(vocab, top_k, top_p):
    k = vocab if top_k == "V" else top_k
    for salt in range(2):
        args = _battery(vocab, k, top_p, salt)
        got, want = np.asarray(_sample(*args)), np.asarray(_reference(*args))
        np.testing.assert_array_equal(got, want)
        sampled = np.asarray(args[1]) > 0
        assert sampled.sum() == B * 5 // 8


def test_policies_mixed_within_a_batch():
    """Every row its own k and p (the engine packs them per slot)."""
    vocab = 1000
    logits, temperature, _, _, seeds, steps = _battery(vocab, 40, 0.95, 7)
    rng = np.random.default_rng(11)
    top_k = jnp.asarray(rng.choice([0, 1, 2, 40, 999, 1000, 5000], B)
                        .astype(np.int32))
    top_p = jnp.asarray(rng.choice([1.0, 0.99, 0.95, 0.5, 0.1, 1e-6], B)
                        .astype(np.float32))
    args = (logits, temperature, top_k, top_p, seeds, steps)
    np.testing.assert_array_equal(np.asarray(_sample(*args)),
                                  np.asarray(_reference(*args)))


def test_a_filter_changes_the_draw():
    """The batteries would pass a sampler that ignored its filters if no
    filter ever moved a token: top-k 40 at p 0.5 must differ from the
    unfiltered draw somewhere."""
    logits, temperature, _, _, seeds, steps = _battery(1000, 0, 1.0, 3)
    free = _sample(logits, temperature, jnp.zeros((B,), jnp.int32),
                   jnp.ones((B,), jnp.float32), seeds, steps)
    cut = _sample(logits, temperature, jnp.full((B,), 40, jnp.int32),
                  jnp.full((B,), 0.5, jnp.float32), seeds, steps)
    assert np.sum(np.asarray(free) != np.asarray(cut)) >= B // 8


# -- greedy ------------------------------------------------------------------

@pytest.mark.parametrize("vocab", VOCABS)
def test_greedy_rows_are_argmax_beside_sampled_rows(vocab):
    logits, temperature, top_k, top_p, seeds, steps = _battery(
        vocab, 40, 0.95, 5)
    # ties on the maximum: argmax takes the lowest id
    logits = logits.at[:, 17].set(jnp.max(logits, axis=-1))
    got = np.asarray(_sample(logits, temperature, top_k, top_p, seeds, steps))
    greedy = np.asarray(temperature) <= 0
    assert greedy.any() and not greedy.all()
    np.testing.assert_array_equal(
        got[greedy], np.argmax(np.asarray(logits), axis=-1)[greedy])


def _policies(vocab=1000):
    return (jax.ShapeDtypeStruct((B, vocab), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.uint32),
            jax.ShapeDtypeStruct((B,), jnp.int32))


def _walk(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def _branches():
    """(the all-greedy branch, the drawn branch) of the one ``cond``."""
    jaxpr = jax.make_jaxpr(sample_tokens)(*_policies()).jaxpr
    conds = [e for e in _walk(jaxpr) if e.primitive.name == "cond"]
    assert len(conds) == 1
    assert not [e for e in jaxpr.eqns if e.primitive.name == "sort"]
    greedy, drawn = conds[0].params["branches"]
    return greedy.jaxpr, drawn.jaxpr


def test_all_greedy_branch_holds_no_sort():
    greedy, _ = _branches()
    names = {e.primitive.name for e in _walk(greedy)}
    assert not names & {"sort", "cumsum", "gather", "scatter",
                        "random_bits", "threefry2x32"}, names


def test_all_greedy_program_keeps_the_conditional():
    """Compiled, the draw still sits in a branch that an all-greedy batch
    does not take: no sort in the entry computation."""
    text = _sample.lower(*_policies()).compile().as_text()
    assert "conditional(" in text
    entry = text[text.index("ENTRY"):]
    entry = entry[:entry.index("\n}")]
    assert " sort(" not in entry
    assert " sort(" in text


def test_drawn_branch_is_one_sort_and_nothing_moved():
    _, drawn = _branches()
    vocab = _policies()[0].shape[-1]
    eqns = list(_walk(drawn))
    names = [e.primitive.name for e in eqns]
    assert names.count("sort") == 1
    assert not [n for n in names if n.startswith("scatter")]
    wide = [e for e in eqns if e.primitive.name == "gather"
            and vocab in e.outvars[0].aval.shape]
    assert not wide, wide


# -- one compile, independent rows --------------------------------------------

def test_policy_changes_do_not_recompile():
    f = jax.jit(lambda *args: sample_tokens(*args))   # a cache of its own
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(B, 1000)).astype(np.float32))
    for i in range(3):
        f(logits + i,
          jnp.asarray(rng.choice([0.0, 0.7, 1.2], B).astype(np.float32))
          * (i > 0),                                   # first call all greedy
          jnp.asarray(rng.integers(0, 50, B).astype(np.int32)),
          jnp.asarray(rng.uniform(0.1, 1.0, B).astype(np.float32)),
          jnp.asarray(rng.integers(0, 2 ** 32, B, dtype=np.uint32)),
          jnp.asarray(rng.integers(0, 100, B).astype(np.int32)))
    assert f._cache_size() == 1


@pytest.mark.parametrize("vocab", VOCABS)
def test_a_rows_token_does_not_depend_on_the_other_rows(vocab):
    args = _battery(vocab, 40, 0.95, 9)
    whole = np.asarray(_sample(*args))
    # the same rows in another order, beside other neighbours
    perm = np.random.default_rng(1).permutation(B)
    moved = np.asarray(_sample(*(a[perm] for a in args)))
    np.testing.assert_array_equal(moved, whole[perm])
    # and with every other row made greedy
    for r in (1, B - 2):
        alone = args[1].at[:].set(0.0).at[r].set(args[1][r])
        got = np.asarray(_sample(args[0], alone, *args[2:]))
        assert got[r] == whole[r]
