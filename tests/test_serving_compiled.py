"""What only COMPILING for the chip shows of the dense block's decode
program: the TPU's compiler is installed here and compiles for a chip that
is described, not attached (no chip time, nothing runs).  The program is
``serve-chat``'s, at its shapes, built as the engine builds it on a TPU.

XLA's layout and memory-space choices are what PR 49 was about: out of the
stacked ``wq``/``wk``/``wv`` it wrote each layer's slice anew every step
and copied it again (0.45 GB, three and a half times over the bus), and
with those gone it staged each layer's whole V pool in VMEM and copied it
back (4.7 GB a step) until the decode program's compile options forbade a
staging that is read less than it copies.  Neither shows in a lowering, in
interpret mode or in any CPU run.

One file, one module-scoped fixture: only one process may load the TPU's
library, so the topology is described inside a test, never at import."""

import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled_decode(one_chip):
    """``serve-chat``'s decode program compiled for the described chip:
    ``(optimised HLO's entry computation, memory analysis)``."""
    from benchmarks import harness
    from distributed_training_sandbox_tpu.models import transformer as T
    from distributed_training_sandbox_tpu.serving import engine as E
    from distributed_training_sandbox_tpu.serving.kv_pool import PagedKVPool
    cell = harness.load_cell("serve-chat", ROOT)
    cfg = harness.model_config(cell.config["fields"])
    eng = cell.traffic["engine"]
    B, page = eng["max_batch"], eng["page_size"]
    P = -(-eng["max_seq_len"] // page)
    sd = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=one_chip)
    i32 = lambda *s: jax.ShapeDtypeStruct(  # noqa: E731
        s, jnp.int32, sharding=one_chip)
    params, bufs = jax.tree.map(sd, jax.eval_shape(lambda: (
        E._dense_serving_tree(T.init_params(jax.random.key(0), cfg),
                              E._decode_cfg(cfg)),
        PagedKVPool(cfg, B * P + 1, page).bufs)))
    backend = jax.default_backend
    jax.default_backend = lambda: "tpu"     # the kernels and options, as there
    try:
        step = E.make_serve_decode_step(cfg, paged_kernel=True)
        compiled = step.trace(
            bufs, params, i32(B, P), i32(B), i32(B), i32(B),
            jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip),
            i32(4 * B)).lower().compile()
    finally:
        jax.default_backend = backend
    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", compiled.as_text(),
                      re.S | re.M).group(1)
    return cfg, bufs, entry, compiled.memory_analysis()


def test_decode_reads_the_qkv_weights_where_they_lie(compiled_decode):
    cfg, _, entry, memory = compiled_decode
    # the stacked three are no operand; nothing writes a layer's weights anew
    for name in ("wq", "wk", "wv"):
        assert f"params__layers____{name}__" not in entry
    assert "params__wqkv___0_" in entry
    H = cfg.hidden_size
    rewritten = [ln for ln in entry.splitlines()
                 if ("slice_bitcast_fusion" in ln and "(%params__" in ln)
                 or re.search(rf"= bf16\[(?:{H},\d{{3,}}|\d{{3,}},{H})\]"
                              r"[^ ]* copy\(", ln)]
    assert not rewritten, rewritten[:3]
    # the parent's program kept 202 MB of re-laid-out slices
    assert memory.temp_size_in_bytes < 64 << 20


def test_decode_never_stages_a_whole_pool_in_vmem(compiled_decode):
    _, bufs, entry, _ = compiled_decode
    pool = ",".join(map(str, bufs.v[0].shape))
    staged = [ln for ln in entry.splitlines() if pool in ln
              and re.search(r"(copy-start|slice-start|copy)\(", ln)]
    assert not staged, staged[:3]
