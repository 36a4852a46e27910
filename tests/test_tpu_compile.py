"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Interpret mode, which every other kernel test runs, never meets Mosaic's
block-layout rules or the chip's memory limits.  These tests lower each
kernel through its public op at production widths (Llama-3-8B heads:
32 query / 8 KV heads of 128; Mamba2-130M's SSD) for a *described*
``v5e:2x2`` topology — the TPU compiler runs here with no chip attached —
and check that the compiled program really contains the Mosaic kernel,
under its ``name=``.  The engine's decode program and the train step are
compiled whole at a small width, and must carry their named scopes in
the ops' metadata, where a device trace finds them.

The topology is described inside a module-scoped fixture (never at import
time): only one process may load the TPU runtime, and every test worker
imports every test file.  The persistent compilation cache is off around
these compiles, since an entry written for a chip cannot be read back
without one.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels.flash import kernel as flash_kernel
from repro.kernels.flash import ops as flash_ops
from repro.kernels.kvq import kernel as kvq_kernel
from repro.kernels.kvq import ops as kvq_ops
from repro.kernels.pack import ops as pack_ops
from repro.kernels.ssd import ops as ssd_ops

# Llama-3-8B attention geometry
H, HKV, D = 32, 8, 128
# Mamba2-130M SSD geometry: d_inner 1536 = 24 heads x 64, d_state 128
SSD_H, SSD_P, SSD_N, SSD_CHUNK = 24, 64, 128, 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; returns the program text."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernels(text: str) -> int:
    return text.count("tpu_custom_call")


def _scoped(text: str, scope: str) -> bool:
    """Whether some op's metadata path holds ``scope`` as one part."""
    rx = re.compile(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)")
    return any(rx.search(n) for n in re.findall(r'op_name="([^"]*)"', text))


def _named_kernels(text: str) -> set:
    """The ``name=`` of every Pallas call in the program."""
    return set(re.findall(r'op_name="[^"]*/([^"/]+)/pallas_call"', text))


def _on_chip(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


#: a small model at real head width, for whole-program compiles
SMALL = dict(n_layers=2, d_model=256, n_heads=4, n_kv=2, head_dim=D,
             d_ff=512, vocab=1024)


@pytest.mark.parametrize("window", [0, 1024])
def test_flash_fwd_compiles(one_chip, window):
    text = _compile(
        lambda q, k, v: flash_ops.flash_attention(
            q, k, v, window=window, backend="pallas"),
        one_chip, ((1, H, 2048, D), jnp.bfloat16),
        ((1, HKV, 2048, D), jnp.bfloat16), ((1, HKV, 2048, D), jnp.bfloat16))
    assert _kernels(text) >= 1


@pytest.mark.parametrize("window", [0, 1024])
def test_flash_fwd_bwd_compiles(one_chip, window):
    def loss(q, k, v):
        o = flash_ops.flash_attention(q, k, v, window=window,
                                      backend="pallas")
        return jnp.sum(o.astype(jnp.float32) ** 2)

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                    ((1, H, 2048, D), jnp.bfloat16),
                    ((1, HKV, 2048, D), jnp.bfloat16),
                    ((1, HKV, 2048, D), jnp.bfloat16))
    assert _kernels(text) >= 4          # fwd, delta, dQ, dKV
    assert _named_kernels(text) == set(flash_kernel.KERNEL_NAMES.values())


@pytest.mark.parametrize("b,s", [(1, 2048), (4, 4096)])
def test_flash_cells_compile_at_chosen_tiles(one_chip, b, s):
    """GLM-4-9B attention (32 query heads on 2 KV heads of 128) at the
    chat cell's longest prefill and the seq4k training step, forward and
    backward, at the tiles ``tiling.flash_tiles`` picks (up to 1024 a
    side): a Mosaic layout or VMEM refusal of those tiles shows here."""
    def loss(q, k, v):
        o = flash_ops.flash_attention(q, k, v, backend="pallas")
        return jnp.sum(o.astype(jnp.float32) ** 2)

    kv = ((b, 2, s, D), jnp.bfloat16)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                    ((b, 32, s, D), jnp.bfloat16), kv, kv)
    assert _named_kernels(text) == set(flash_kernel.KERNEL_NAMES.values())


def test_flash_resid_bf16_backward_compiles(one_chip):
    """The ``resid_bf16`` policy at the seq4k cell's length: f32 compute
    with q, k, v and o saved in bf16, so the backward kernels meet an f32
    dO against bf16 tiles (widened to f32 for dO·vᵀ)."""
    def loss(q, k, v):
        o = flash_ops.flash_attention(q, k, v, backend="pallas",
                                      resid_dtype="bfloat16")
        return jnp.sum(o ** 2)

    kv = ((1, 2, 4096, D), jnp.float32)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                    ((1, 32, 4096, D), jnp.float32), kv, kv)
    assert _named_kernels(text) == set(flash_kernel.KERNEL_NAMES.values())


@pytest.mark.parametrize("bq", [128, None])
def test_flash_fp16_is_refused(one_chip, bq):
    """Mosaic on a v5e refuses to load f16 tiles, on 128 x 128 tiles as
    on the chosen ones, so the ``fp16`` policy cannot take the compiled
    flash path on this chip; this pins that down."""
    def fwd(q, k, v):
        return flash_kernel.flash_attention_fwd_pallas(q, k, v, bq=bq,
                                                      bk=bq)[0]

    kv = ((2, 2048, D), jnp.float16)
    with pytest.raises(Exception, match="Mosaic failed to compile"):
        _compile(fwd, one_chip, ((32, 2048, D), jnp.float16), kv, kv)


@pytest.mark.parametrize("s,d", [(256, 32), (40, 64)])
def test_flash_small_shapes_compile(one_chip, s, d):
    """A head_dim under the 128-lane tile and a sequence under one block
    lower too: their blocks span the whole array dimension."""
    def loss(q, k, v):
        return jnp.sum(flash_ops.flash_attention(
            q, k, v, backend="pallas").astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                    *[((1, 2, s, d), jnp.bfloat16)] * 3)
    assert _kernels(text) >= 4


@pytest.mark.parametrize("splits", [1, 4])
@pytest.mark.parametrize("with_lengths", [False, True])
def test_kvq_decode_compiles(one_chip, splits, with_lengths):
    b, s = 8, 2048

    def decode(q, kq, ks, vq, vs, lengths):
        return kvq_ops.decode_attention(
            q, kq, ks, vq, vs, backend="pallas", splits=splits,
            lengths=lengths if with_lengths else None)

    text = _compile(decode, one_chip, ((b, H, D), jnp.bfloat16),
                    ((b, HKV, s, D), jnp.int8), ((b, HKV, s), jnp.float32),
                    ((b, HKV, s, D), jnp.int8), ((b, HKV, s), jnp.float32),
                    ((b,), jnp.int32))
    assert _kernels(text) == 1
    assert _named_kernels(text) == {kvq_kernel.KERNEL_NAME}


def test_pack_encode_compiles(one_chip):
    text = _compile(lambda x: pack_ops.encode(x, backend="pallas"), one_chip,
                    ((1024, 32, 32, 3), jnp.uint8))
    assert _kernels(text) == 1


def test_pack_decode_compiles(one_chip):
    text = _compile(lambda x: pack_ops.decode(x, backend="pallas"), one_chip,
                    ((256, 32, 32, 3), jnp.uint32))
    assert _kernels(text) == 1


def test_ssd_fwd_compiles(one_chip):
    b, L = 2, 2048

    def run(x, dt, a, bm, cm, d):
        return ssd_ops.ssd(x, dt, a, bm, cm, d, chunk=SSD_CHUNK,
                           backend="pallas")

    text = _compile(run, one_chip,
                    ((b, L, SSD_H, SSD_P), jnp.float32),
                    ((b, L, SSD_H), jnp.float32), ((SSD_H,), jnp.float32),
                    ((b, L, SSD_N), jnp.float32), ((b, L, SSD_N), jnp.float32),
                    ((SSD_H,), jnp.float32))
    assert _kernels(text) == 1


def test_decode_program_names_its_phases(one_chip):
    """The engine's whole decode round: the named kvq kernel, and the
    ``layers``, ``lm_head``, ``sample`` and ``sentinel`` scopes."""
    from repro.models import transformer
    from repro.serve import ServeEngine

    cfg = dataclasses.replace(configs.smoke_config("llama3-8b"), **SMALL)
    params = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.bfloat16), params)
    eng = ServeEngine(params, cfg, max_slots=8, max_len=1024,
                      prompt_buckets=(128,), kv_backend="pallas")
    text = eng._decode_fn.lower(*_on_chip(
        (eng.params, eng.pool.cache, eng._tokens_dev, eng._active_dev,
         eng._key), one_chip)).compile().as_text()
    assert _named_kernels(text) == {kvq_kernel.KERNEL_NAME}
    for scope in ("layers", "lm_head", "sample", "sentinel"):
        assert _scoped(text, scope), scope


def test_train_step_names_its_phases(one_chip):
    """The whole train step: the named flash kernels, the LM head with
    its loss (forward and backward) and the optimizer."""
    from repro.core.mixed_precision import LossScale
    from repro.models import transformer
    from repro.optim import adamw
    from repro.train.train_step import TrainConfig, build_train_step

    cfg = dataclasses.replace(configs.smoke_config("llama3-8b"), **SMALL,
                              attn_backend="pallas")
    params = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    opt = jax.eval_shape(adamw.init, params)
    batch = {k: jax.ShapeDtypeStruct((2, 256), jnp.int32)
             for k in ("tokens", "labels")}
    step = build_train_step(cfg, TrainConfig())
    text = jax.jit(step).lower(*_on_chip(
        (params, opt, LossScale.noop(), batch), one_chip)).compile().as_text()
    assert _named_kernels(text) == set(flash_kernel.KERNEL_NAMES.values())
    assert _scoped(text, "lm_head_loss") and _scoped(text, "optimizer")
    assert re.search(r'op_name="[^"]*transpose\(jvp\(lm_head_loss\)\)',
                     text)


# GLM-4.7-Flash latent attention: 20 heads, a 512-wide latent and 64 rope
# dims per position; prefill attends at head dim 192 + 64 = 256
MLA_H, MLA_C, MLA_R, MLA_D = 20, 512, 64, 256


def test_mla_decode_compiles_at_cell_size(one_chip):
    """The latent decode kernel at the long-context cell's pool: 24 slots
    of 16384 positions, 12 layers, reading layer 3 where it lies."""
    from repro.kernels.mla import kernel as mla_kernel
    from repro.kernels.mla import ops as mla_ops

    b, n_layers, s = 24, 12, 16384
    bf16 = jnp.bfloat16
    text = _compile(
        lambda qa, qr, lat, rope, lens: mla_ops.mla_decode_attention(
            qa, qr, lat, rope, lens, 3, sm_scale=MLA_D ** -0.5,
            backend="pallas"),
        one_chip, ((b, MLA_H, MLA_C), bf16), ((b, MLA_H, MLA_R), bf16),
        ((n_layers, b, s, MLA_C), bf16), ((n_layers, b, MLA_R, s), bf16),
        ((b,), jnp.int32))
    assert _named_kernels(text) == {mla_kernel.KERNEL_NAME}
    assert not re.search(r"= bf16\[12,24,16384,\d+\][^=]*copy\(", text)


def test_flash_fwd_compiles_at_mla_prefill(one_chip):
    """Latent attention's prefill as flash sees it: 20 heads of 256 over
    the cell's largest bucket."""
    shape = ((1, MLA_H, 16384, MLA_D), jnp.bfloat16)
    text = _compile(lambda q, k, v: flash_ops.flash_attention(
        q, k, v, causal=True, sm_scale=MLA_D ** -0.5, backend="pallas"),
        one_chip, shape, shape, shape)
    assert _named_kernels(text) == {flash_kernel.KERNEL_NAMES["fwd"]}


def _mla_cfg(**kw):
    from repro.models.config import MLAConfig, MoEConfig
    base = configs.get_config("glm47-flash")
    return dataclasses.replace(
        base, mla=MLAConfig(q_lora_rank=768, kv_lora_rank=MLA_C,
                            qk_nope_dim=192, qk_rope_dim=MLA_R,
                            v_head_dim=256),
        moe=dataclasses.replace(base.moe, held=(8, 8)), **kw)


def test_mla_decode_program_names_its_phases(one_chip):
    """The engine's decode round for latent attention with a dense
    prologue and held experts: the named latent kernel, and the
    ``layers``, ``mla_decode``, ``moe``, ``lm_head`` and ``sentinel``
    scopes."""
    from repro.kernels.mla import kernel as mla_kernel
    from repro.models import transformer
    from repro.serve import ServeEngine

    cfg = _mla_cfg(n_layers=3, d_model=256, d_ff=512, vocab=1024)
    params = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.bfloat16), params)
    eng = ServeEngine(params, cfg, max_slots=8, max_len=1024,
                      prompt_buckets=(128,), kv_backend="pallas")
    text = eng._decode_fn.lower(*_on_chip(
        (eng.params, eng.pool.cache, eng._tokens_dev, eng._active_dev,
         eng._key), one_chip)).compile().as_text()
    assert _named_kernels(text) == {mla_kernel.KERNEL_NAME}
    for scope in ("layers", "mla_decode", "moe", "lm_head", "sentinel"):
        assert _scoped(text, scope), scope


def test_mla_decode_round_copies_no_cache(one_chip):
    """The long-context cell's decode round at its size (12 layers, 24
    slots of 16384): the 5.4 GB latent cache is donated, written and read
    in place; the round's temporaries stay under 0.1 GB."""
    from repro.core.mixed_precision import get_policy
    from repro.models import transformer

    cfg = _mla_cfg(n_layers=12)
    b, s = 24, 16384
    params = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16),
        transformer.init_params(cfg, jax.random.PRNGKey(0))))
    cache = jax.eval_shape(lambda: transformer.init_cache(cfg, b, s))
    cache["pos"] = jax.ShapeDtypeStruct((b,), jnp.int32)

    def decode(params, cache, tokens, active):
        return transformer.decode_step(
            params, cfg, cache, tokens, policy=get_policy("bf16"),
            kvq_backend="pallas", active=active)

    compiled = jax.jit(decode, donate_argnums=(1,)).lower(*_on_chip(
        (params, cache, jax.ShapeDtypeStruct((b,), jnp.int32),
         jax.ShapeDtypeStruct((b,), jnp.bool_)), one_chip)).compile()
    text = compiled.as_text()
    assert not re.search(r"= bf16\[12,24,(16384,512|64,16384)\]"
                         r"[^=]*copy(-start)?\(", text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 12 * b * s * (MLA_C + MLA_R) * 2
    assert mem.temp_size_in_bytes < 1e8


def test_gqa_decode_round_copies_no_cache(one_chip):
    """The chat cell's decode round at its size (GLM-4-9B, 16 layers, 64
    slots of 2560, 2 int8 KV heads of 128): the 0.67 GB K and V leaves and
    their scales are donated, written and read in place; the round's
    temporaries stay under 0.1 GB."""
    from repro.core.mixed_precision import get_policy
    from repro.models import transformer

    cfg = dataclasses.replace(configs.get_config("glm4-9b"), n_layers=16,
                              vocab=151552)
    b, s = 64, 2560
    assert (cfg.n_kv, cfg.head_dim) == (2, D)
    params = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16),
        transformer.init_params(cfg, jax.random.PRNGKey(0))))
    cache = jax.eval_shape(lambda: transformer.init_cache(cfg, b, s))
    cache["pos"] = jax.ShapeDtypeStruct((b,), jnp.int32)

    def decode(params, cache, tokens, active):
        return transformer.decode_step(
            params, cfg, cache, tokens, policy=get_policy("bf16"),
            kvq_backend="pallas", active=active)

    compiled = jax.jit(decode, donate_argnums=(1,)).lower(*_on_chip(
        (params, cache, jax.ShapeDtypeStruct((b,), jnp.int32),
         jax.ShapeDtypeStruct((b,), jnp.bool_)), one_chip)).compile()
    text = compiled.as_text()
    assert _named_kernels(text) == {kvq_kernel.KERNEL_NAME}
    assert not re.search(r"= (s8\[16,64,2,2560,128\]|f32\[16,64,2,2560\])"
                         r"[^=]*copy(-start)?\(", text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 16 * b * 2 * s * (2 * D + 2 * 4)
    assert mem.temp_size_in_bytes < 1e8
