"""The kernels the per-layer rooflines are about, compiled at the cells'
real widths for the v5e by the TPU compiler that is installed here — no
chip, so nothing runs and nothing here is a time.  What the compiler
would refuse on the chip it refuses here, at no chip time.

The topology is described inside a fixture (never at import: only one
process may load the TPU's library), and every such test is in this one
file."""

import pytest

from bench_helpers import load_config
from benchmark import fabricate


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any refusal means: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without a chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)


def _model(config_name):
    from distributed_llm_dissemination_tpu.models.llama import ModelConfig

    config = load_config(config_name)
    m = fabricate.model_dims(config)
    return config, ModelConfig(
        name=config_name, vocab=m["vocab"], d_model=m["d"],
        n_layers=m["layers"], n_heads=m["h"], n_kv_heads=m["kv"],
        d_ff=m["f"], rope_theta=m["theta"], norm_eps=m["eps"])


@pytest.mark.parametrize("config_name", ["mistral-7b-v0.3-d8",
                                         "codestral-22b-v0.1-d9"])
def test_ingest_splice_compiles_at_a_layer_blobs_size(one_chip, config_name):
    import jax
    import jax.numpy as jnp

    from distributed_llm_dissemination_tpu.parallel.ingest import _concat_pad

    config, _ = _model(config_name)
    total = fabricate.blob_nbytes(config, 0)
    frag = 64 << 20  # a striped fragment
    sizes = [frag] * (total // frag) + ([total % frag] if total % frag
                                        else [])
    pieces = [jax.ShapeDtypeStruct((n,), jnp.uint8, sharding=one_chip)
              for n in sizes]
    pad = -(-total // 1024) * 1024
    compiled = _concat_pad.lower(pieces, pad=pad).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= total
    assert mem.temp_size_in_bytes < 16e9


@pytest.mark.parametrize("codec", fabricate.CODECS)
def test_device_decode_compiles_for_a_mistral_layer(one_chip, codec):
    import jax
    import jax.numpy as jnp

    from distributed_llm_dissemination_tpu.models import quant, serde

    config, cfg = _model("mistral-7b-v0.3-d8")
    nbytes = fabricate.blob_nbytes(config, 0, codec)
    assert nbytes == quant.blob_nbytes_codec(cfg, 0, codec)
    blob = jax.ShapeDtypeStruct((nbytes,), jnp.uint8, sharding=one_chip)
    specs = tuple((n, tuple(s)) for n, s in serde.layer_param_specs(cfg))
    compiled = quant.device_decode_jit(codec).lower(
        (blob,), specs, "bfloat16").compile()
    mem = compiled.memory_analysis()
    # every decoded bfloat16 parameter of the layer comes out
    assert mem.output_size_in_bytes >= fabricate.blob_nbytes(config, 0)
    # and the decode's scratch stays far inside a 16 GB chip
    assert mem.temp_size_in_bytes < 4e9
