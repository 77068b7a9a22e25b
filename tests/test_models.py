"""Model + 5-axis sharded train-step tests on the 8-device CPU mesh."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_dissemination_tpu.models.llama import (
    CONFIGS,
    forward_jit,
    init_params,
    loss_fn,
)
from distributed_llm_dissemination_tpu.models.sharded import (
    build_train_step,
    example_batch,
    factor_mesh_axes,
    make_train_mesh,
    param_specs,
    shard_params,
)


@pytest.mark.parametrize("name", ["tiny", "tiny-moe"])
def test_forward_shapes_finite(name, cpu_devices):
    cfg = CONFIGS[name]
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab)
    logits = forward_jit(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())


def test_layer_sizes_match_baseline_shapes():
    # BASELINE.json configs: 8B layers ~400 MiB, 70B layers ~1.6 GiB.
    mib = CONFIGS["llama3-8b"].layer_nbytes() / (1 << 20)
    gib70 = CONFIGS["llama3-70b"].layer_nbytes() / (1 << 30)
    assert 380 <= mib <= 440
    assert 1.5 <= gib70 <= 1.7
    assert CONFIGS["llama3-405b"].n_layers == 126


def test_factor_mesh_axes_tiny():
    cfg = CONFIGS["tiny"]
    assert factor_mesh_axes(1, cfg) == {"dp": 1, "sp": 1, "pp": 1, "ep": 1, "tp": 1}
    eight = factor_mesh_axes(8, cfg)
    assert eight["tp"] == 2 and eight["pp"] == 2 and eight["sp"] == 2
    moe16 = factor_mesh_axes(16, CONFIGS["tiny-moe"])
    assert moe16["ep"] == 2  # ep activates once experts exist
    # tp never exceeds kv heads; pp never exceeds layers.
    assert factor_mesh_axes(64, cfg)["tp"] <= cfg.n_kv_heads
    assert factor_mesh_axes(64, cfg)["pp"] <= cfg.n_layers


@pytest.mark.parametrize("name,tol", [("tiny", 1e-3), ("tiny-moe", 2e-2)])
def test_sharded_loss_matches_unsharded(name, tol, cpu_devices):
    # The 5-axis manual shard_map program must agree with the plain
    # single-device forward (bf16 reduction-order tolerance).
    cfg = CONFIGS[name]
    mesh = make_train_mesh(8, cfg)
    params = init_params(cfg, jax.random.key(0))
    step = build_train_step(cfg, mesh, lr=0.0)
    inputs, targets = example_batch(cfg, mesh)
    tokens = jnp.concatenate(
        [np.asarray(inputs), np.asarray(targets)[:, -1:]], axis=1
    )
    l_ref = float(loss_fn(params, tokens, cfg))  # before donation
    _, l_sharded = step(shard_params(params, mesh, cfg), inputs, targets)
    assert abs(float(l_sharded) - l_ref) < tol


@pytest.mark.parametrize("name", ["tiny", "tiny-moe"])
def test_sharded_training_decreases_loss(name, cpu_devices):
    cfg = CONFIGS[name]
    mesh = make_train_mesh(8, cfg)
    params = shard_params(init_params(cfg, jax.random.key(0)), mesh, cfg)
    step = build_train_step(cfg, mesh, lr=1e-2)
    inputs, targets = example_batch(cfg, mesh)
    params, first = step(params, inputs, targets)
    last = first
    for _ in range(4):
        params, last = step(params, inputs, targets)
    assert float(last) < float(first)


def test_remat_train_step_matches_non_remat(cpu_devices):
    """jax.checkpoint on the scanned layer must be a pure memory/FLOPs
    trade: identical params and loss after a step (same reduction
    order — the recompute replays the same program).

    Bit-exactness holds on runtimes whose remat replays the identical
    program; the 0.4.x line re-fuses the recompute on CPU and drifts by
    ~1 ulp in float32 (observed max 1.5e-8 abs) — there the assertion
    is a tight allclose instead of exact, still far below any training-
    visible difference."""
    import dataclasses

    exact = jax.__version_info__ >= (0, 5)
    cfg = dataclasses.replace(CONFIGS["tiny"], dtype=jnp.float32)
    mesh = make_train_mesh(8, cfg)
    inputs, targets = example_batch(cfg, mesh)
    outs = {}
    for remat in (False, True):
        params = shard_params(init_params(cfg, jax.random.key(0)),
                              mesh, cfg)
        step = build_train_step(cfg, mesh, lr=1e-2, remat=remat)
        params, loss = step(params, inputs, targets)
        outs[remat] = (jax.tree.map(np.asarray, params), float(loss))
    if exact:
        assert outs[False][1] == outs[True][1]
    else:
        np.testing.assert_allclose(outs[False][1], outs[True][1],
                                   rtol=1e-6, atol=0)
    for (pa, a), (pb, b) in zip(
        jax.tree_util.tree_flatten_with_path(outs[False][0])[0],
        jax.tree_util.tree_flatten_with_path(outs[True][0])[0],
    ):
        if exact:
            np.testing.assert_array_equal(a, b, err_msg=str(pa))
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7,
                                       err_msg=str(pa))


@pytest.mark.parametrize("name", ["tiny", "tiny-moe"])
def test_adamw_train_step_decreases_loss_and_shards_moments(
        name, cpu_devices):
    """The AdamW step trains (loss decreases over a few steps) and its
    moments are sharded exactly like their params — optimizer state
    never concentrates on one device."""
    from distributed_llm_dissemination_tpu.models.sharded import (
        build_adamw_train_step,
        init_adamw_state,
    )

    cfg = CONFIGS[name]
    mesh = make_train_mesh(8, cfg)
    params = shard_params(init_params(cfg, jax.random.key(0)), mesh, cfg)
    opt = init_adamw_state(params)
    step = build_adamw_train_step(cfg, mesh, lr=3e-3)
    inputs, targets = example_batch(cfg, mesh)
    params, opt, first = step(params, opt, inputs, targets)
    last = first
    for _ in range(4):
        params, opt, last = step(params, opt, inputs, targets)
    assert float(last) < float(first)
    assert int(opt["step"]) == 5
    # Moments shard like their params (same per-leaf sharding).
    for (path, p), (_, m) in zip(
        jax.tree_util.tree_flatten_with_path(params)[0],
        jax.tree_util.tree_flatten_with_path(opt["m"])[0],
    ):
        assert m.sharding == p.sharding, path
        assert m.dtype == jnp.float32


def test_adamw_matches_reference_adamw_unsharded(cpu_devices):
    """One AdamW step on the 8-device mesh must match a straightforward
    single-device AdamW applied to jax.grad of the unsharded loss."""
    import dataclasses

    from distributed_llm_dissemination_tpu.models.sharded import (
        build_adamw_train_step,
        init_adamw_state,
    )

    cfg = dataclasses.replace(CONFIGS["tiny"], dtype=jnp.float32)
    mesh = make_train_mesh(8, cfg)
    params = init_params(cfg, jax.random.key(0))
    inputs, targets = example_batch(cfg, mesh)
    tokens = jnp.concatenate(
        [np.asarray(inputs), np.asarray(targets)[:, -1:]], axis=1
    )
    # eps at 1e-3 (not the training default 1e-8): with tiny first-step
    # moments, m/(sqrt(v)+eps) ~ sign(g), and the sharded loss's f32
    # reduction-order noise (~1e-4 rel on grads) would be amplified to
    # ~sign flips near zero.  A conditioning eps keeps the comparison
    # linear in the gradient, so this asserts the OPTIMIZER math, not
    # reduction-order luck.
    lr, b1, b2, eps, wd = 1e-2, 0.9, 0.999, 1e-3, 0.01
    grads = jax.grad(loss_fn)(params, tokens, cfg)
    want = {}
    for (path, p), (_, g) in zip(
        jax.tree_util.tree_flatten_with_path(params)[0],
        jax.tree_util.tree_flatten_with_path(grads)[0],
    ):
        m = (1 - b1) * g
        v = (1 - b2) * g * g
        step_dir = (m / (1 - b1)) / (jnp.sqrt(v / (1 - b2)) + eps)
        want[str(path)] = np.asarray(p - lr * (step_dir + wd * p))

    sharded = shard_params(params, mesh, cfg)
    opt = init_adamw_state(sharded)
    step = build_adamw_train_step(cfg, mesh, lr=lr, betas=(b1, b2),
                                  eps=eps, weight_decay=wd)
    new_params, _, _ = step(sharded, opt, inputs, targets)
    for path, got in jax.tree_util.tree_flatten_with_path(new_params)[0]:
        ref = want[str(path)]
        scale = float(np.abs(ref).max()) + 1e-30
        rel = float(np.abs(np.asarray(got) - ref).max()) / scale
        assert rel < 1e-4, f"{path}: {rel}"


@pytest.mark.parametrize("name", ["tiny", "tiny-moe"])
def test_sharded_gradients_exact(name, cpu_devices):
    # Gradients (not just loss) must match jax.grad of the unsharded loss:
    # update magnitude = (old - new)/lr compared leaf-by-leaf in fp32.
    # Guards against replication double-counting (an earlier bug scaled
    # grads by the device count).
    import dataclasses

    from distributed_llm_dissemination_tpu.models.llama import CONFIGS as C

    cfg = dataclasses.replace(C[name], dtype=jnp.float32)
    mesh = make_train_mesh(8, cfg)
    params = init_params(cfg, jax.random.key(0))
    lr = 1.0
    step = build_train_step(cfg, mesh, lr=lr)
    inputs, targets = example_batch(cfg, mesh)
    tokens = jnp.concatenate(
        [np.asarray(inputs), np.asarray(targets)[:, -1:]], axis=1
    )
    ref_grads = jax.grad(loss_fn)(params, tokens, cfg)  # before donation
    # Snapshot to host: donation may alias and delete the original buffers.
    old_params = jax.tree.map(np.asarray, params)
    new_params, _ = step(shard_params(params, mesh, cfg), inputs, targets)
    for (path, old), (_, new), (_, ref) in zip(
        jax.tree_util.tree_flatten_with_path(old_params)[0],
        jax.tree_util.tree_flatten_with_path(new_params)[0],
        jax.tree_util.tree_flatten_with_path(ref_grads)[0],
    ):
        got = (old - np.asarray(new)) / lr
        scale = float(jnp.abs(ref).max()) + 1e-30
        rel = float(jnp.abs(got - ref).max()) / scale
        name_str = "/".join(str(getattr(k, "key", k)) for k in path)
        assert rel < 1e-4, f"{name_str}: grad relative error {rel}"


def test_param_specs_cover_all_leaves(cpu_devices):
    cfg = CONFIGS["tiny-moe"]
    params = init_params(cfg, jax.random.key(0))
    specs = param_specs(cfg)
    from jax.sharding import PartitionSpec as P

    p_leaves, p_tree = jax.tree.flatten(params)
    s_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(p_leaves) == len(s_leaves)
    # Every layer-stack leaf leads with the pp axis.
    for path, spec in zip(jax.tree_util.tree_flatten_with_path(params)[0], s_leaves):
        keys = [getattr(k, "key", None) for k in path[0]]
        if "layers" in keys:
            assert spec[0] == "pp"


# Lengths in words around the two grains a widening can have: a 128-word
# row and the kernel's 4 KiB tile (2048 2-byte words), and one that
# holds every 16-bit pattern.
WIDEN_WORDS = (2, 127, 128, 129, 2047, 2048, 2049, 4096, 4096 + 3,
               65536, 65536 + 3)


@pytest.mark.parametrize("words", WIDEN_WORDS)
@pytest.mark.parametrize("itemsize", (1, 2, 4))
def test_bytes_to_wide_bit_exact_all_widths(itemsize, words):
    # The decode primitive behind every device blob assembly
    # (serde._bytes_to_wide): whole tiles through the widening kernel,
    # the rest through the strided byte combine, then a same-width
    # bitcast — must reproduce a little-endian memory view BIT-exactly
    # at every length.  Compared through integer dtypes — the TPU float
    # path canonicalizes NaN bit patterns, and this pin must hold on
    # every backend.
    from distributed_llm_dissemination_tpu.models import serde

    rng = np.random.default_rng(7 + words)
    if itemsize == 2:
        # all 65,536 patterns, in a shuffled order, repeated or cut
        buf = np.resize(rng.permutation(65536).astype(np.uint16),
                        words).view(np.uint8)
    else:
        buf = rng.integers(0, 256, words * itemsize, dtype=np.uint8)
    uint = {1: np.uint8, 2: np.uint16, 4: np.uint32}[itemsize]
    fast, slow = serde.widen_split(len(buf), itemsize)
    assert fast + slow == (len(buf) if itemsize > 1 else 0)
    assert fast % 4096 == 0 and (slow < 4096 or itemsize == 4)
    # the integer views, and the float widths real checkpoints use
    for dt in {1: (jnp.int8,), 2: (jnp.uint16, jnp.bfloat16),
               4: (jnp.uint32, jnp.float32)}[itemsize]:
        got = np.asarray(serde._bytes_to_wide(jnp.asarray(buf), dt))
        np.testing.assert_array_equal(got.view(uint), buf.view(uint),
                                      err_msg=str(dt))


def test_bytes_to_wide_rejects_8_byte_items():
    # uint64 silently truncates without jax_enable_x64; no config uses
    # 8-byte widths, so they are refused loudly.
    from distributed_llm_dissemination_tpu.models import serde

    with pytest.raises(ValueError, match="itemsize 8"):
        serde._bytes_to_wide(jnp.zeros(4096, jnp.uint8), jnp.float64)


def _as_u16(tree):
    return {k: np.asarray(jax.device_get(v)).view(np.uint16)
            for k, v in tree.items()}


def _assert_same_bf16_bits(got, want, name):
    """Equal bit for bit, but for a NaN's payload: a copy of a bfloat16
    array may quieten a signalling NaN (the TPU's does, the CPU's
    sometimes), in the slices' path as in the kernel's — the widening
    itself is pinned through integers above."""
    nan = ((want & 0x7F80) == 0x7F80) & ((want & 0x7F) != 0)
    np.testing.assert_array_equal(got[~nan], want[~nan], err_msg=name)
    assert np.all((got[nan] & 0x7F80) == 0x7F80), name
    assert np.all((got[nan] & 0x7F) != 0), name
    assert np.all((got[nan] & 0x8000) == (want[nan] & 0x8000)), name


def test_decode_blobs_equals_the_host_views_bit_for_bit():
    # serde._decode_blobs (the device path) against params_from_blobs'
    # numpy views (the host path) on RANDOM bytes, NaN patterns too:
    # two stacked layer blobs whose leaves are longer than a tile
    # (32 KiB) and shorter (the 256-byte norms), and a head blob whose
    # embedding is fifteen tiles and a remainder.
    from distributed_llm_dissemination_tpu.models import serde

    cfg = dataclasses.replace(CONFIGS["tiny"], n_layers=2, vocab=250)
    rng = np.random.default_rng(11)
    blobs = {b: rng.integers(0, 256, serde.blob_nbytes(cfg, b),
                             dtype=np.uint8).tobytes()
             for b in range(cfg.n_layers + 1)}
    want = serde.params_from_blobs(cfg, blobs)
    dev = {b: jnp.asarray(np.frombuffer(blobs[b], np.uint8)) for b in blobs}
    specs = tuple(serde.layer_param_specs(cfg))
    sizes = [serde.widen_split(int(np.prod(s)) * 2, 2) for _, s in specs]
    assert any(f and not sl for f, sl in sizes)
    assert any(sl and not f for f, sl in sizes)
    got = serde._decode_blobs((dev[0], dev[1]), specs, "bfloat16")
    for name, arr in _as_u16(got).items():
        _assert_same_bf16_bits(arr, want["layers"][name].view(np.uint16),
                               name)
    head_specs = tuple(serde.head_param_specs(cfg))
    assert all(serde.widen_split(250 * cfg.d_model * 2, 2))  # both paths
    head = serde._decode_blobs((dev[2],), head_specs, "bfloat16")
    for name, arr in _as_u16(head).items():
        _assert_same_bf16_bits(arr[0], want[name].view(np.uint16), name)


def test_decode_programs_keep_their_names_and_stride_no_bytes():
    # Shapes only, lowered for this backend: one Mistral-7B leaf
    # (4096 x 14336 bfloat16) through _decode_blobs.  A stride on a
    # uint8 operand is the lane-by-lane gather that cost 0.12 s a leaf
    # on the v5e (PR 24's ledger lines); whole tiles must never take it.
    # The traced names are what the benchmark's trace reader and the
    # compile-log oracles find the programs by.
    import re

    from distributed_llm_dissemination_tpu.models import quant, serde

    shape = (4096, 14336)
    blob = jax.ShapeDtypeStruct((shape[0] * shape[1] * 2,), jnp.uint8)
    text = serde._decode_blobs.lower(
        (blob,), (("w1", shape),), "bfloat16").as_text()
    assert "@jit__decode_blobs" in text
    strided = [
        m.group(0) for m in re.finditer(
            r"stablehlo\.slice[^\n]*?\[([^\]]*)\]\s*:\s*\(tensor<[0-9x]*ui8>",
            text)
        if any(len(dim.split(":")) == 3 and dim.split(":")[2].strip() != "1"
               for dim in m.group(1).split(","))]
    assert not strided, strided[:2]
    # ... and the pattern does see the strided form where it is kept
    tail = serde._decode_blobs.lower(
        (jax.ShapeDtypeStruct((128,), jnp.uint8),), (("ln", (64,)),),
        "bfloat16").as_text()
    assert re.search(r"stablehlo\.slice[^\n]*:2\]\s*:\s*\(tensor<128xui8>",
                     tail)
    qblob = jax.ShapeDtypeStruct((64 * 4 + 64 * 128,), jnp.uint8)
    qtext = quant._decode_qblobs.lower(
        (qblob,), (("wq", (64, 128)),), "bfloat16").as_text()
    assert "@jit__decode_qblobs" in qtext
    assert quant.device_decode_jit("raw") is serde._decode_blobs
    assert quant.device_decode_jit("int8") is quant._decode_qblobs


def test_train_state_checkpoint_roundtrip_resumes_exactly(
        cpu_devices, tmp_path):
    """Save (params, AdamW state) mid-run, restore onto the mesh, and
    continue: the resumed trajectory must be bit-identical to the
    uninterrupted one (training durability, the other half of the
    dissemination layer's byte-level resume)."""
    from distributed_llm_dissemination_tpu.models.sharded import (
        build_adamw_train_step,
        init_adamw_state,
    )
    from distributed_llm_dissemination_tpu.models.train_ckpt import (
        restore_train_state,
        save_train_state,
    )

    cfg = CONFIGS["tiny"]
    mesh = make_train_mesh(8, cfg)
    step = build_adamw_train_step(cfg, mesh, lr=3e-3)
    inputs, targets = example_batch(cfg, mesh)

    params = shard_params(init_params(cfg, jax.random.key(0)), mesh, cfg)
    opt = init_adamw_state(params)
    for _ in range(2):
        params, opt, _ = step(params, opt, inputs, targets)
    path = str(tmp_path / "trainstate")
    save_train_state(path, params, opt)

    # Uninterrupted continuation (reference trajectory).
    ref_params, ref_opt = params, opt
    ref_params, ref_opt, ref_loss = step(ref_params, ref_opt,
                                         inputs, targets)

    # Restored continuation: same mesh, state from disk, placed with
    # the train step's shardings (equivalence, not spec spelling —
    # P('pp') and P('pp', None) are the same placement).
    from distributed_llm_dissemination_tpu.models.train_ckpt import (
        _state_shardings,
    )

    got_params, got_opt = restore_train_state(path, cfg, mesh)
    assert int(got_opt["step"]) == 2
    for (pa, a), (_, sh) in zip(
        jax.tree_util.tree_flatten_with_path(got_params)[0],
        jax.tree_util.tree_flatten_with_path(_state_shardings(cfg, mesh)["params"])[0],
    ):
        assert a.sharding.is_equivalent_to(sh, a.ndim), pa
    got_params, got_opt, got_loss = step(got_params, got_opt,
                                         inputs, targets)
    assert float(got_loss) == float(ref_loss)
    for (pa, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(got_params)[0],
        jax.tree_util.tree_flatten_with_path(ref_params)[0],
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(pa))
