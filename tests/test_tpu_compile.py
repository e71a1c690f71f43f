"""The main path's Pallas kernels compile for a v5e — without a v5e.

The TPU compiler is installed wherever libtpu is, and compiles for a
chip that is described (``v5e:2x2``) and not attached. That catches what
interpret mode cannot: misaligned tiles, more VMEM than a kernel may
use, a kernel that quietly dispatched to its reference (no
``tpu_custom_call`` in the program). Nothing runs, so nothing here says
anything about results or speed — ``tests/test_tpu_hardware.py`` and
``chip_smoke.py`` do that on the chip.

libtpu takes a machine-wide lock for the life of the process that loads
it, and a second process that tries gets an error. So each case compiles
in a short-lived child (this file, run as a script) and the children
queue on a file lock: the cases stay correct when a parallel runner
spreads them over workers. The child forces the kernels' ``_interpret``
switch off — code that asks ``jax.default_backend()`` still sees the CPU
here — and turns the persistent compile cache off, which cannot read
such entries back without a chip.
"""

import fcntl
import importlib
import json
import math
import os
import subprocess
import sys
import tempfile

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ACTS = ("relu", "relu", "softmax")
FCNN = (784, 128, 64, 10)


def _fused_dense(m, k, n, activation):
    def build(S, jnp):
        from tpu_dist_nn.kernels.fused_dense import fused_dense

        fn = lambda x, w, b: fused_dense(x, w, b, activation=activation)  # noqa: E731
        return fn, (S((m, k)), S((k, n)), S((n,)))
    return build


def _chain(batch, int8):
    def build(S, jnp):
        from tpu_dist_nn.kernels.fused_dense import fcnn_fused_forward
        from tpu_dist_nn.kernels.quantized import fcnn_quantized_forward

        dims = list(zip(FCNN, FCNN[1:]))
        if int8:
            params = [
                {"wq": S((a, b), jnp.int8), "scale": S((b,)), "b": S((b,))}
                for a, b in dims
            ]
            fn = lambda p, x: fcnn_quantized_forward(  # noqa: E731
                p, x, activations=ACTS, prefer_kernel=True
            )
        else:
            params = [{"w": S((a, b)), "b": S((b,))} for a, b in dims]
            fn = lambda p, x: fcnn_fused_forward(p, x, activations=ACTS)  # noqa: E731
        return fn, (params, S((batch, FCNN[0])))
    return build


def _flash(t, heads, head_dim, dtype, grad):
    def build(S, jnp):
        import jax

        fa = importlib.import_module("tpu_dist_nn.kernels.flash_attention")
        seq = t if t else fa.max_seq_len(
            head_dim, jnp.dtype(dtype), backward=grad
        )
        fwd = lambda q, k, v: fa.flash_attention(q, k, v, causal=True)  # noqa: E731
        fn = jax.grad(
            lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        ) if grad else fwd
        return fn, (S((1, seq, heads, head_dim), jnp.dtype(dtype)),) * 3
    return build


def _conv(S, jnp):
    from tpu_dist_nn.kernels.conv2d import fused_conv2d

    fn = lambda imgs, w, b: fused_conv2d(  # noqa: E731
        imgs, w, b, padding="same", activation="relu", pool_window=(2, 2)
    )
    return fn, (S((64, 16, 16, 32)), S((3, 3, 32, 64)), S((64,)))


def _kv_write(S, jnp):
    from tpu_dist_nn.kernels.kv_write import write_rows

    # gpt2-large's heads, a partial last lane block (383 = 2 * 128 + 127),
    # pool slots behind the request region.
    cache = S((2, 64, 20, 64, 383), jnp.bfloat16)
    new = S((2, 56, 20, 64), jnp.bfloat16)
    return write_rows, (cache, cache, new, new, S((56,), jnp.int32),
                        S((56,), jnp.bool_))


def _slot_step(S, jnp):
    """The scheduler's step program at gpt2-large's widths, three layers
    deep, with a prefix pool: a Mosaic call writes the rows, and nothing
    else in the compiled program yields a layer of the cache."""
    import jax

    from tools.aot_step_ops import big_ops
    from tpu_dist_nn.models.generate import init_slot_cache
    from tpu_dist_nn.models.transformer import (
        TransformerConfig,
        init_transformer,
    )
    from tpu_dist_nn.serving.continuous import slot_kernels

    cfg = TransformerConfig(
        vocab_size=1024, d_model=1280, n_heads=20, n_layers=3, d_ff=5120,
        max_seq_len=1024, compute_dtype="bfloat16",
    )
    slots, pool, extent = 56, 8, 383

    def shaped(tree):
        return jax.tree.map(lambda a: S(a.shape, a.dtype), tree)

    params = shaped(jax.eval_shape(
        lambda: init_transformer(jax.random.key(0), cfg)))
    cache = shaped(jax.eval_shape(
        lambda: init_slot_cache(cfg, slots + pool, extent)))
    key = shaped(jax.eval_shape(lambda: jax.random.key(0)))
    _, _, step = slot_kernels(cfg, 0.0, None, None)
    ints = S((slots,), jnp.int32)

    def check(text):
        layer = slots * extent * cfg.n_heads * cfg.head_dim
        found = big_ops(text, layer)["ops"]
        return [f"{op['name']} ({op['opcode']}) {op['shapes']}"
                for op in found
                if op["opcode"] != "custom-call tpu_custom_call"]

    # As the loop launches it: the tokens of the step and of the chunk
    # launched before, still on the device, come in beside `tok`.
    args = (params, cache, ints, S((slots,), jnp.bool_), ints, key, ints,
            S((), jnp.int32))
    return step, args, check


def _sala_programs(S, jnp):
    """The MiniCPM-SALA family at the published attention widths (32
    query heads on 2 K/V heads of 128, 32 lightning heads of 128) and the
    benchmark cell's 16 slots of extent 33 023, four layers deep on a
    narrow residual, with a prefix pool: the scheduler's three programs,
    and the shapes of their parameters, cache and key."""
    import jax

    from tpu_dist_nn.models import sala
    from tpu_dist_nn.serving.continuous import slot_kernels

    cfg = sala.SalaConfig(
        vocab_size=1024, hidden_size=1024, intermediate_size=2048,
        n_heads=32, n_kv_heads=2, head_dim=128, lightning_heads=32,
        lightning_head_dim=128,
        mixer_types=(sala.SPARSE, sala.LIGHTNING, sala.LIGHTNING,
                     sala.SPARSE),
        layer_ids=(9, 10, 15, 16), published_layers=32, max_seq_len=524288,
        scale_emb=12.0, scale_depth=1.4,
    )
    slots, pool, extent = 16, 2, 33023

    def shaped(tree):
        return jax.tree.map(lambda a: S(a.shape, a.dtype), tree)

    params = shaped(jax.eval_shape(
        lambda: sala.init_sala(jax.random.key(0), cfg)))
    cache = shaped(jax.eval_shape(
        lambda: sala.init_slot_cache(cfg, slots + pool, extent)))
    key = shaped(jax.eval_shape(lambda: jax.random.key(0)))
    return cfg, slots, slot_kernels(cfg, 0.0, None, None), params, cache, key


def _sala_step(S, jnp):
    """The scheduler's step program for that family: a Mosaic call
    writes the K/V rows, and nothing else in the compiled program yields
    a layer of K or of V (no gather, slice or re-layout of the rows the
    selection and the attention read where they lie)."""
    from tools.aot_step_ops import big_ops

    cfg, slots, (_, _, step), params, cache, key = _sala_programs(S, jnp)
    ints = S((slots,), jnp.int32)

    def check(text):
        layer = slots * cfg.n_kv_heads * cfg.head_dim * 33024
        found = big_ops(text, layer)["ops"]
        return [f"{op['name']} ({op['opcode']}) {op['shapes']}"
                for op in found
                if op["opcode"] != "custom-call tpu_custom_call"]

    args = (params, cache, ints, S((slots,), jnp.bool_), ints, key, ints,
            S((), jnp.int32))
    return step, args, check


def _sala_chunk(S, jnp):
    """The scheduler's chunk program for that family at the cell's
    shapes (a chunk of 2048, `start` traced): the sparse layers'
    attention is the Mosaic call of kernels/sparse_attend.py, which
    fits VMEM at the tiles it picks (the compile would refuse it), and
    nothing is left in HBM of the XLA loop's float32 temporaries `(G 2,
    group 16, C 2048, ...)`: a key tile's scores, or the accumulator
    that loop carried."""
    from tools.aot_step_ops import big_ops

    cfg, _, (chunk, _, _), params, cache, key = _sala_programs(S, jnp)
    assert cfg.attend_kernel(2048, 33023)

    def check(text):
        problems = [] if "sparse_attend" in text else [
            "no custom call named sparse_attend"]
        found = big_ops(text, 2 * 16 * 2048 * 128)["ops"]
        return problems + [
            f"{op['name']} ({op['opcode']}) {op['shapes']}" for op in found
            if any(d == "f32" and dims[:3] == (2, 16, 2048)
                   for d, dims, _, _ in op["shapes"])]

    args = (params, cache, S((), jnp.int32), S((1, 2048), jnp.int32),
            S((), jnp.int32), key)
    return chunk, args, check


def _sambay_step(S, jnp):
    """The scheduler's step program for the SambaY family at the
    published attention widths (40 query heads on 20 K/V heads of 64,
    rings of 512) and the benchmark cell's 96 slots of extent 3071,
    eight layers deep on a narrow MLP, with a prefix pool: two Mosaic
    calls write the ring rows at `pos mod 512` and the shared K/V rows
    at `pos`, and nothing else in the compiled program yields a layer of
    a ring (nor, eight times that, the shared K or V): the rows are
    read where they lie, the shared K/V by the Mosaic call of
    kernels/decode_attend.py (the full layer's and the cross layers'
    scan body's), which fits VMEM at the tile it picks, and nothing is
    left of the float32 scores `(96, 20, 2, 3072)` XLA kept in HBM."""
    import jax

    from tools.aot_step_ops import big_ops
    from tpu_dist_nn.models import sambay
    from tpu_dist_nn.serving.continuous import slot_kernels

    cfg = sambay.SambaYConfig(
        vocab_size=1024, hidden_size=2560, intermediate_size=512,
        n_heads=40, n_kv_heads=20, n_layers=8, sliding_window=512,
        max_seq_len=262144,
    )
    slots, pool, extent = 96, 4, 3071

    def shaped(tree):
        return jax.tree.map(lambda a: S(a.shape, a.dtype), tree)

    params = shaped(jax.eval_shape(
        lambda: sambay.init_sambay(jax.random.key(0), cfg)))
    cache = shaped(jax.eval_shape(
        lambda: sambay.init_slot_cache(cfg, slots + pool, extent)))
    key = shaped(jax.eval_shape(lambda: jax.random.key(0)))
    _, _, step = slot_kernels(cfg, 0.0, None, None)
    ints = S((slots,), jnp.int32)

    def check(text):
        layer = slots * cfg.n_kv_heads * cfg.head_dim * cfg.sliding_window
        found = big_ops(text, layer)["ops"]
        calls = [op for op in found
                 if op["opcode"] == "custom-call tpu_custom_call"]
        problems = [] if len(calls) == 2 else [
            f"{len(calls)} row writes at cache size, not the rings' and "
            "the shared K/V's"]
        if text.count('custom_call_target="tpu_custom_call"') != 4 \
                or "decode_attend" not in text:
            problems.append("not two row writes and two decode_attend calls")
        scores = slots * cfg.n_kv_heads * 2 * 3072
        return problems + [
            f"{op['name']} ({op['opcode']}) {op['shapes']}"
            for op in found if op not in calls] + [
            f"scores in HBM: {op['name']} {op['shapes']}"
            for op in big_ops(text, scores)["ops"]
            if any(d == "f32" and dims[-1] == 3072
                   for d, dims, _, _ in op["shapes"])]

    args = (params, cache, ints, S((slots,), jnp.bool_), ints, key, ints,
            S((), jnp.int32))
    return step, args, check


def _mla_moe_config():
    from tpu_dist_nn.models import mla_moe

    # The published attention and expert widths, one dense and two
    # expert layers, a narrow dense FFN and a small vocabulary.
    return mla_moe.MlaMoeConfig(
        vocab_size=1024, hidden_size=7168, n_heads=64, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, intermediate_size=512, moe_intermediate_size=2048,
        n_layers=3, first_k_dense=1, router_width=384,
        experts_held=tuple(range(12)), n_experts_per_tok=8,
        routed_scaling_factor=2.827, max_seq_len=262144, rope_theta=50000.0,
        rope_factor=64.0, rope_mscale_all_dim=1.0)


def _mla_moe_programs(S, jnp):
    import jax

    from tpu_dist_nn.models import mla_moe
    from tpu_dist_nn.serving.continuous import slot_kernels

    cfg = _mla_moe_config()
    slots, pool, extent = 48, 2, 9215

    def shaped(tree):
        return jax.tree.map(lambda a: S(a.shape, a.dtype), tree)

    params = shaped(jax.eval_shape(
        lambda: mla_moe.init_mla_moe(jax.random.key(0), cfg)))
    cache = shaped(jax.eval_shape(
        lambda: mla_moe.init_slot_cache(cfg, slots + pool, extent)))
    key = shaped(jax.eval_shape(lambda: jax.random.key(0)))
    chunk, _, step = slot_kernels(cfg, 0.0, None, None)
    return cfg, slots, params, cache, key, chunk, step


def _mla_moe_step(S, jnp):
    """The scheduler's step program for the latent-attention family at
    the published widths (64 heads on one 576-wide latent row) and the
    benchmark cell's 48 slots of extent 9215, with a prefix pool: one
    Mosaic call writes the new rows of every layer at `pos`, the Mosaic
    call of kernels/latent_attend.py (the dense layer's and the expert
    layers' scan body's) reads the rows where they lie, layer by its
    scalar index, and nothing else in the compiled program yields a
    layer of latent rows (no slice of a layer handed to the call, no
    copy of the cache beside the in-place write), nor is anything left
    of the float32 scores `(48, 1, 64, 9217)` XLA kept in HBM."""
    from tools.aot_step_ops import big_ops

    cfg, slots, params, cache, key, _, step = _mla_moe_programs(S, jnp)
    ints = S((slots,), jnp.int32)

    def check(text):
        layer = slots * cfg.latent_dim * 9216
        found = big_ops(text, layer)["ops"]
        calls = [op for op in found
                 if op["opcode"] == "custom-call tpu_custom_call"]
        problems = [] if len(calls) == 1 and "kv_write_row" in text else [
            f"{len(calls)} row writes at cache size, not one"]
        if text.count('custom_call_target="tpu_custom_call"') != 3 \
                or "latent_attend" not in text:
            problems.append("not one row write and two latent_attend calls")
        scores = slots * cfg.n_heads * 9216
        return problems + [
            f"{op['name']} ({op['opcode']}) {op['shapes']}"
            for op in found if op not in calls] + [
            f"scores in HBM: {op['name']} {op['shapes']}"
            for op in big_ops(text, scores)["ops"]
            if any(d == "f32" and dims[-1] in (9216, 9217)
                   for d, dims, _, _ in op["shapes"])]

    args = (params, cache, ints, S((slots,), jnp.bool_), ints, key, ints,
            S((), jnp.int32))
    return step, args, check


def _mla_moe_chunk(S, jnp):
    """The chunk program of the same family at a chunk of 1024: the
    slot's rows are written in place, the ragged expert product slices
    one expert's matrices inside each tile's products and never copies a
    layer of them (12 x 7168 x 4096: 0.7 GB a layer when it did), and
    the expanded attention is the Mosaic call `expand_attend`: no key,
    value or score of a key tile is an XLA value (float32 scores `(64,
    1024, 512)` were, 134 MB a tile)."""
    from tools.aot_step_ops import big_ops

    cfg, slots, params, cache, key, chunk, _ = _mla_moe_programs(S, jnp)

    def check(text):
        experts = cfg.n_held * cfg.hidden_size * cfg.moe_intermediate_size
        scores = (cfg.n_heads, 1024, 512)

        def unwanted(op):
            # A key tile's scores, or anything of a layer of experts'
            # size that is not the cache.
            dims = [d for _, d, _, _ in op["shapes"]]
            return any(d[-3:] == scores for d in dims) or (
                max(n for *_, n in op["shapes"]) >= experts
                and not any(d[:2] == (cfg.n_layers, slots + 2)
                            for d in dims))

        return ([] if "expand_attend" in text else [
            "no expand_attend call: the chunk fell to the XLA loop"]) + [
            f"{op['name']} ({op['opcode']}) {op['shapes']}"
            for op in big_ops(text, math.prod(scores))["ops"]
            if unwanted(op)]

    args = (params, cache, S((), jnp.int32), S((1, 1024), jnp.int32),
            S((), jnp.int32), key)
    return chunk, args, check



def _laguna_programs(S, jnp):
    import jax

    from tpu_dist_nn.models import laguna
    from tpu_dist_nn.serving.continuous import slot_kernels

    # The published attention widths and the cell's five layer kinds
    # (48 and 72 query heads over 8 K/V heads of 128, a window of 512),
    # 8 of the router's 256 experts held, a narrow dense FFN and a small
    # vocabulary; the cell's 16 slots of extent 17407.
    full, window = laguna.FULL, laguna.WINDOW
    cfg = laguna.LagunaConfig(
        vocab_size=1024, hidden_size=3072, head_dim=128, n_kv_heads=8,
        layer_types=(full, window, window, window, full),
        mlp_layer_types=("dense",) + ("sparse",) * 4,
        heads_per_layer=(48, 72, 72, 72, 48), intermediate_size=512,
        moe_intermediate_size=1024, shared_intermediate_size=1024,
        router_width=256, experts_held=tuple(range(8)), n_experts_per_tok=10,
        routed_scaling_factor=2.5, sliding_window=512, max_seq_len=1048576,
        rope_factor=128.0, attention_factor=1.4852030263919618)
    slots, extent = 16, 17407

    def shaped(tree):
        return jax.tree.map(lambda a: S(a.shape, a.dtype), tree)

    params = shaped(jax.eval_shape(
        lambda: laguna.init_laguna(jax.random.key(0), cfg)))
    cache = shaped(jax.eval_shape(
        lambda: laguna.init_slot_cache(cfg, slots, extent)))
    key = shaped(jax.eval_shape(lambda: jax.random.key(0)))
    chunk, _, step = slot_kernels(cfg, 0.0, None, None)
    return cfg, slots, params, cache, key, chunk, step


def _not_the_cache(text, cfg, slots):
    """What the compiled program yields at a layer of K rows' size or
    more, other than the full layers' K/V cache itself."""
    from tools.aot_step_ops import big_ops

    layer = slots * cfg.n_kv_heads * cfg.head_dim * 17408
    return [op for op in big_ops(text, layer)["ops"]
            if not all(dims[:2] == (cfg.n_full, slots)
                       for _, dims, _, _ in op["shapes"])]


def _laguna_step(S, jnp):
    """The scheduler's step program for Laguna's family at the published
    attention widths and the cell's 16 slots of extent 17407: the new
    rows land by two Mosaic calls (the full layers' K/V at `pos`, the
    window layers' rings at `pos mod 512`), the one at cache size in
    place, and nothing else in the compiled program yields a layer of
    K/V rows: the full layers read their rows where they lie."""
    cfg, slots, params, cache, key, _, step = _laguna_programs(S, jnp)
    ints = S((slots,), jnp.int32)

    def check(text):
        problems = [] if text.count(
            'custom_call_target="tpu_custom_call"') == 2 else [
            "not two row writes"]
        return problems + [f"{op['name']} ({op['opcode']}) {op['shapes']}"
                           for op in _not_the_cache(text, cfg, slots)
                           if "kv_write_rows" not in op["name"]]

    args = (params, cache, ints, S((slots,), jnp.bool_), ints, key, ints,
            S((), jnp.int32))
    return step, args, check


def _sparse_attend_small_tiles(S, jnp):
    """The kernel alone at the smallest tiles its dispatch keeps (query
    128, key 384: an extent of 1152 that 768 does not divide); the cell's
    512 x 768 compiles inside `sala_chunk_...` below."""
    from tpu_dist_nn.kernels import sparse_attend

    assert sparse_attend.tiles(128, 16, 128, 1152, 64) == (128, 384)
    rows = S((2, 128, 1152), jnp.bfloat16)
    return (lambda q, k, v, sel, start: sparse_attend.attend_chunk(
        q, k, v, sel, start, 64)), (
        S((128, 2, 16, 128), jnp.bfloat16), rows, rows,
        S((128, 2, 18), jnp.bool_), S((), jnp.int32))


def _expand_attend_cell(S, jnp):
    """The kernel alone at the repository cell's shape and tiling: 64
    heads in groups of 8, a chunk of 1024 whole in VMEM in query tiles of
    512, key tiles of 1024 of an extent of 9216."""
    from tpu_dist_nn.kernels import expand_attend

    shape = (1024, 64, 512, 128, 64, 128, 9216)
    assert expand_attend.tiles(*shape, jnp.bfloat16) == (8, 512, 1024)
    C, H, rkv, dn, dr, dv, M = shape
    bf = jnp.bfloat16
    return (lambda *a: expand_attend.attend_chunk(*a, 0.1)), (
        S((C, H, dn), bf), S((C, H, dr), bf), S((1, rkv + dr, M), bf),
        S((rkv, H, dn), bf), S((rkv, H, dv), bf), S((), jnp.int32))


def _decode_attend_one_slot(S, jnp):
    """The kernel alone as a final chunk's tail runs it: one slot of a
    cache of 96 (the step's 96 slots compile inside `sambay_step_...`
    below), float32 rows as the CPU tests hold them."""
    from tpu_dist_nn.kernels import decode_attend

    assert decode_attend.tiles(1, 20, 64, 3072, jnp.float32) == 512
    cache = S((1, 96, 20, 64, 3072))
    own = S((1, 20, 64))
    return decode_attend.attend_rows, (
        S((1, 10, 2, 2, 64)), cache, cache, own, own, S((1,), jnp.int32),
        S(()))


def _latent_attend_cell(S, jnp):
    """The kernel alone at the repository cell's step: 48 slots of a
    cache of 50 and six layers, 64 heads on 576-wide rows of which 512
    are values, an extent of 9216 in tiles of 1024."""
    from tpu_dist_nn.kernels import latent_attend

    assert latent_attend.tiles(48, 64, 576, 512, 9216, jnp.bfloat16) == 1024
    bf = jnp.bfloat16
    return (lambda *a: latent_attend.attend_rows(*a, 512, 0.1)), (
        S((48, 64, 576), bf), S((6, 50, 1, 576, 9216), bf),
        S((), jnp.int32), S((48, 576), bf), S((48,), jnp.int32))


CASES = {
    "fused_dense_256x784x128_relu": _fused_dense(256, 784, 128, "relu"),
    "fused_dense_256x64x10_softmax": _fused_dense(256, 64, 10, "softmax"),
    "fused_dense_100x784x128_relu": _fused_dense(100, 784, 128, "relu"),
    "f32_chain_b256": _chain(256, int8=False),
    "f32_chain_b8192": _chain(8192, int8=False),
    "int8_chain_b512": _chain(512, int8=True),
    "conv_16x16x32_to_64_pool": _conv,
    "flash_t4096_h12_d64_bf16_fwd": _flash(4096, 12, 64, "bfloat16", False),
    "flash_t4096_h12_d64_bf16_grad": _flash(4096, 12, 64, "bfloat16", True),
    # Past the compiler's 16 MiB default: refused before the kernels
    # raised their scoped-VMEM limit.
    "flash_t8192_h12_d64_bf16_grad": _flash(8192, 12, 64, "bfloat16", True),
    "flash_t8192_h8_d32_f32_fwd": _flash(8192, 8, 32, "float32", False),
    # The ceiling flash_attention admits is one the compiler accepts.
    "flash_ceiling_d64_bf16_fwd": _flash(None, 2, 64, "bfloat16", False),
    "flash_ceiling_d64_bf16_grad": _flash(None, 2, 64, "bfloat16", True),
    "flash_ceiling_d256_f32_grad": _flash(None, 2, 256, "float32", True),
    "kv_write_h20_d64_m383_bf16": _kv_write,
    "slot_step_h20_d1280_in_place": _slot_step,
    "sala_step_g2_d128_m33023_in_place": _sala_step,
    "sala_chunk_c2048_g2_d128_m33023_attend_kernel": _sala_chunk,
    "sparse_attend_q128_k384_smallest_tiles": _sparse_attend_small_tiles,
    "sambay_step_g20_d64_w512_m3071_in_place": _sambay_step,
    "decode_attend_one_slot_g20_d64_m3072_f32": _decode_attend_one_slot,
    "mla_moe_step_h64_r576_m9215_in_place": _mla_moe_step,
    "mla_moe_chunk_c1024_r576_m9215_expand_kernel": _mla_moe_chunk,
    "expand_attend_c1024_h64_r576_m9216_bf16": _expand_attend_cell,
    "latent_attend_s48_h64_r576_m9216_bf16": _latent_attend_cell,
    "laguna_step_g8_h48_72_w512_m17407_in_place": _laguna_step,
}


def _compile(case: str) -> dict:
    """Child side: compile one case for a described v5e."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # The chip runs at default matmul precision. The CPU suite's
    # "highest" would reach the kernels too, and Mosaic refuses an int8
    # matmul at fp32 contract precision.
    os.environ.pop("JAX_DEFAULT_MATMUL_PRECISION", None)
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    for name in ("conv2d", "flash_attention", "fused_dense", "quantized"):
        importlib.import_module(
            f"tpu_dist_nn.kernels.{name}"
        )._interpret = lambda: False
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no compiler here: the parent skips
        return {"skip": f"{type(e).__name__}: {e}"[:300]}
    chip = SingleDeviceSharding(topo.devices[0])

    def S(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    fn, args, *check = CASES[case](S, jnp)
    # A program that is jitted already (with its donation) lowers as it is.
    fn = fn if hasattr(fn, "lower") else jax.jit(fn)
    try:
        text = fn.lower(*args).compile().as_text()
    except Exception as e:  # noqa: BLE001 — reported, the parent fails
        return {"error": f"{type(e).__name__}: {e}"[:2000]}
    out = {"custom_call": "tpu_custom_call" in text}
    problems = check[0](text) if check else []
    if problems:
        out["problems"] = problems
    return out


def _compiled_in_a_child(case: str) -> dict:
    lock = os.path.join(tempfile.gettempdir(), "tdn_tpu_compile.lock")
    with open(lock, "a") as held:
        fcntl.flock(held, fcntl.LOCK_EX)  # released when the file closes
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), case],
            capture_output=True, text=True, timeout=300,
        )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    if "skip" in out:
        pytest.skip(f"no described v5e here: {out['skip']}")
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_to_a_mosaic_call_for_v5e(case):
    out = _compiled_in_a_child(case)
    assert out == {"custom_call": True}, out


if __name__ == "__main__":
    print(json.dumps(_compile(sys.argv[1])))
