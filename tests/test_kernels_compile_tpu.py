"""Every Pallas kernel compiles for a v5e, checked without a chip; and the
chip smoke's own phases run, at a toy size, on the CPU mesh.

The installed libtpu compiles for a TPU topology with no device attached
(``jax.experimental.topologies``), so a kernel Mosaic would refuse on the
chip fails here first — in under a second per kernel, inside tier-1.  The
kernel cases and their shapes are ``chip_smoke.kernel_cases``: what is
compiled here is what ``python chip_smoke.py`` runs on the chip.
"""

import json
import os
import re
import sys

import jax
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from apex_tpu.utils import platform  # noqa: E402


@pytest.fixture(scope="module")
def v5e_device():
    """One device of a detached v5e 2x2 topology.  A failure to build it
    is a failure of the test, not a skip: without it nothing in tier-1
    says whether the kernels still compile for the chip."""
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    assert len(topo.devices) == 4
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices[0]


def _cases():
    """The smoke's kernel cases at the one-chip head count, plus those
    whose shapes depend on it at one tp=4 rank's share."""
    full = chip_smoke.FULL
    names = [case.name for case in chip_smoke.kernel_cases(full)]
    return [pytest.param(name, full.heads, id=name) for name in names] + [
        pytest.param(name, full.heads // 4, id=f"{name}-tp4")
        for name in names if name.startswith(("paged_", "flash_"))]


@pytest.mark.parametrize("name,heads", _cases())
def test_kernel_compiles_for_v5e(name, heads, v5e_device, monkeypatch):
    # compiled, not interpreted, although the default backend is the CPU
    monkeypatch.setattr(platform, "pallas_interpret", lambda: False)
    case = next(c for c in chip_smoke.kernel_cases(chip_smoke.FULL, heads)
                if c.name == name)
    sharding = SingleDeviceSharding(v5e_device)
    args, kwargs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        jax.eval_shape(case.make_args))
    lowered = jax.jit(case.kernel).lower(*args, **kwargs)
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()


# the cache-group kernels and the expert layer at the widths that brought
# them (ISSUE 27): 64 query heads, q/k 192 beside v 128, blocks of 16,
# a table of 384 blocks; full: 4 KV heads; window: 8, 128 tokens, sinks
GROUP_KERNELS = [
    pytest.param(kind, name, g, blocks, window, id=f"{kind}_{name}")
    for kind in ("decode", "prefill")
    for name, g, blocks, window in (("full", 4, 16384, None),
                                    ("window", 8, 1152, 128))]


def custom_call_configs(lowered):
    """``{kernel name: custom_call_config}`` of a lowered program's Mosaic
    calls."""
    return {name: json.loads(config.replace("\\22", '"'))[
                "custom_call_config"]
            for config, name in re.findall(
                r'@tpu_custom_call\(.*?backend_config = "(.*?)", '
                r'kernel_name = "([\w.]+)"', lowered.as_text())}


def assert_copies_unchecked(lowered, name, copies):
    """The cache groups' decode kernel issues its page copies without
    Mosaic's run-time bounds checks (ISSUE 36): the plan is clamped to the
    arena, so every index is in range by construction.  ``copies``: the
    page copies a step issues at these widths, as the gauge the trace set
    says."""
    from apex_tpu.observability.metrics import default_registry

    configs = custom_call_configs(lowered)
    assert list(configs) == [name]
    assert configs[name]["disable_bounds_checks"] is True
    kind = name.rsplit("_", 1)[1]
    assert default_registry().snapshot()[
        f"paged_decode/copies_per_step/{kind}"] == copies


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_latent_kernel_compiles_for_v5e_at_published_widths(
        kind, v5e_device, monkeypatch):
    """The latent group's kernels at the widths that brought them (ISSUE
    33): 128 heads over rows of 512 + 64 channels in 640 lanes, blocks of
    16, a table of 1,280 blocks; prefill a walk of 8 slots."""
    import jax.numpy as jnp

    from apex_tpu.serving import paged_attention as pa

    monkeypatch.setattr(platform, "pallas_interpret", lambda: False)
    sharding = SingleDeviceSharding(v5e_device)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    b, n, lanes, bs, chunk = (64, 128, 640, 16, 128)
    kw = dict(v_dim=512, scale=0.1147)
    arena = shape((53248, bs, lanes))
    if kind == "decode":
        def call(q, rows, tables, lengths):
            return pa.paged_decode_latent(q, rows, tables, lengths, **kw)
        args = (shape((b, n, lanes)), arena, shape((b, 1280), jnp.int32),
                shape((b,), jnp.int32))
    else:
        b = 8

        def call(q, rows, tables, lengths, limits):
            return pa.paged_prefill_latent(q, rows, tables, lengths, limits,
                                           **kw)
        args = (shape((b, chunk, n, lanes)), arena,
                shape((b, 1280), jnp.int32), shape((b,), jnp.int32),
                shape((b, chunk), jnp.int32))
    lowered = jax.jit(call).lower(*args)
    if kind == "decode":
        assert_copies_unchecked(lowered, "paged_decode_latent", 64)
    assert f"%paged_{kind}_latent" in lowered.compile().as_text()


@pytest.mark.parametrize("kind,name,g,blocks,window", GROUP_KERNELS)
def test_group_kernel_compiles_for_v5e_at_published_widths(
        kind, name, g, blocks, window, v5e_device, monkeypatch):
    import jax.numpy as jnp

    from apex_tpu.serving import paged_attention as pa

    monkeypatch.setattr(platform, "pallas_interpret", lambda: False)
    sharding = SingleDeviceSharding(v5e_device)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    b, n, dk, dv, bs, chunk = 64, 64, 192, 128, 16, 128
    cache = (shape((blocks, bs, g * dk)), shape((blocks, bs, g * dv)),
             shape((b, 384), jnp.int32), shape((b,), jnp.int32))
    kw = dict(kv_heads=g, window=window)
    sinks = shape((n,), jnp.float32)
    if kind == "decode":
        def call(q, k, v, tables, lengths, sinks):
            return pa.paged_attention_decode(
                q, k, v, tables, lengths,
                sinks=sinks if window else None, **kw)
        args = (shape((b, n, dk)),) + cache + (sinks,)
    else:
        def call(q, k, v, tables, lengths, limits, sinks):
            return pa.paged_prefill_attention(
                q, k, v, tables, lengths, limits,
                sinks=sinks if window else None, **kw)
        args = (shape((b, chunk, n, dk)),) + cache + (
            shape((b, chunk), jnp.int32), sinks)
    lowered = jax.jit(call).lower(*args)
    if kind == "decode":
        assert_copies_unchecked(lowered, f"paged_decode_{name}",
                                {"full": 64, "window": 18}[name])
    assert f"%paged_{kind}_{name}" in lowered.compile().as_text()


def test_expert_layer_compiles_for_v5e_at_published_widths(
        v5e_device, monkeypatch):
    import jax.numpy as jnp

    from apex_tpu.transformer.moe import held_experts_ffn

    monkeypatch.setattr(platform, "pallas_interpret", lambda: False)
    sharding = SingleDeviceSharding(v5e_device)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    def layer(x, router, bias, gate_up, down):
        return held_experts_ffn(x, router, bias, gate_up, down, top_k=8,
                                held=(0, 16))

    compiled = jax.jit(layer).lower(
        shape((64, 4096)), shape((4096, 256)), shape((256,)),
        shape((16, 4096, 4096)), shape((16, 2048, 4096))).compile()
    # the two grouped matmuls, named by the scope the trace reads
    assert compiled.as_text().count(" custom-call(") >= 2
    assert "%moe_experts" in compiled.as_text()


# the flash kernels with a window and grouped-query heads, and the grouped
# matmul's backward, at the shapes that brought them (ISSUE 31): one
# sequence of 8192, 32 query heads on 4 K/V heads of 128, window 2048;
# 12288 sorted rows over 16 experts of 2048 x 2 * 1024 and 1024 x 2048
@pytest.mark.parametrize("window", [2048, None], ids=["window", "full"])
def test_flash_window_kernels_compile_for_v5e_at_published_widths(
        window, v5e_device, monkeypatch):
    import jax.numpy as jnp

    from apex_tpu.ops.flash_attention import flash_attention

    monkeypatch.setattr(platform, "pallas_interpret", lambda: False)
    sharding = SingleDeviceSharding(v5e_device)
    q = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16,
                             sharding=sharding)
    kv = jax.ShapeDtypeStruct((1, 4, 8192, 128), jnp.bfloat16,
                              sharding=sharding)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=window)
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    # forward, dq, dkv
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_grouped_matmul_backward_compiles_for_v5e_at_published_widths(
        v5e_device, monkeypatch):
    import jax.numpy as jnp

    from apex_tpu.transformer.moe import held_experts_ffn

    monkeypatch.setattr(platform, "pallas_interpret", lambda: False)
    sharding = SingleDeviceSharding(v5e_device)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    def loss(x, router, gate_up, down, shared_up, shared_down, bias):
        y, _, _, _ = held_experts_ffn(
            x, router, bias, gate_up, down, top_k=8, held=(0, 16),
            route_eps=1e-20, route_scale=2.826,
            shared=(shared_up, shared_down))
        return jnp.sum(y)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
        shape((8192, 2048)), shape((2048, 128), jnp.float32),
        shape((16, 2048, 2048)), shape((16, 1024, 2048)),
        shape((2048, 2048)), shape((1024, 2048)),
        shape((128,), jnp.float32)).compile()
    text = compiled.as_text()
    assert "%moe_experts" in text and "%moe_experts_bwd" in text


TOY = chip_smoke.Sizes(
    hidden=64, layers=4, heads=4, vocab=256, positions=64, batch=4,
    microbatches=2, train_steps=3, lr=1e-3, max_batch=4, max_seq=64,
    prefill_len=16, requests=((0, 40), (0, 5), (1, 33), (3, 18)),
    new_tokens=6, verify_k=2, lora_rank=4)


def test_chip_smoke_phases_at_toy_size():
    """The phase functions ``chip_smoke.main`` calls, unchanged, on four
    virtual CPU devices: the four-chip host's layout (pp2 x tp2 trainer,
    tp4 server) with the kernels interpreted."""
    devices = jax.devices()[:4]
    train = chip_smoke.train_phase(TOY, devices)
    assert train["layout"] == {"dp": 1, "pp": 2, "vpp": 2, "tp": 2}
    assert train["losses"][-1] < train["losses"][0]
    serve = chip_smoke.serve_phase(TOY, devices)
    assert serve["tokens_out"] == len(TOY.requests) * TOY.new_tokens
    errors = chip_smoke.kernel_phase(TOY, TOY.heads // len(devices))
    assert len(errors) == len(chip_smoke.kernel_cases(TOY))


def test_chip_smoke_refuses_to_run_without_a_tpu():
    with pytest.raises(SystemExit, match="no TPU"):
        chip_smoke.main()
