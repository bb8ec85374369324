"""Where ZeRO-1 puts dp, read from the program the TPU compiler makes of it
(models/gpt_hybrid.py: ``moment_specs``, ``_grads_to_owner``): the
``dp=2 x tp=2 + sp, zero1`` train step of ``train-6p7b-s2048-4chip`` at the
cell's widths and 4 of its 16 layers, compiled for a described v5e:2x2 and
not run. With the moments sharded inside each layer, a layer's dp gradient
sum is a reduce-scatter into the owner's shard (on the TPU a fusion that
calls ``%all-reduce-scatter.N``), where the parent all-reduced the whole
gradient over dp and then sent the reduced layer to the rank that owned it.
The counts recorded from the parent are of the same compile at commit
0cead87 (PERF.md, section 6, PR 31).

The second test needs no chip: with ``dp == 1`` nothing here may change the
lowered step."""
import functools
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.models import gpt_hybrid as gh
from paddle_tpu.models.gpt import GPTConfig

LAYERS = 4
#: replica groups of the two dp peers on the (dp, pp, tp) mesh of a 2x2
DP_GROUPS = ("[2,2]<=[2,2]T(1,0)", "{{0,2},{1,3}}")
#: the same compile of the parent, 4 layers: gathers of the whole qkv
#: weight (one a layer), gathers of the sequence-sharded activations, bytes
#: a device
PARENT = {"ag_4096_12288": 4, "ag_2_2048_4096": 28, "bytes": 4_877_555_200}


def _pcfg(**kw):
    return gh.ParallelConfig(**{**dict(
        dp=2, pp=1, tp=2, sp=True, zero1=True, remat=True,
        remat_policy="names", scan_unroll=16, fused_ce=True,
        param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16,
        moment_dtype=None), **kw})


def compile_cell_step(layers=LAYERS):
    """The cell's train step at ``layers`` layers, compiled for a described
    v5e:2x2 (raises where none can be described). At 16 layers this is
    the chip's program as far as the records can tell (PERF.md, section 6,
    PR 31): ``python tests/test_zero1_reduce_scatter.py 16`` prints its
    memory and gathers."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cfg = GPTConfig(vocab_size=50304, hidden_size=4096, num_layers=layers,
                    num_heads=32, max_seq_len=2048, ffn_mult=4)
    pcfg = _pcfg()
    mesh = gh.build_mesh(pcfg, topo.devices)
    shapes = jax.eval_shape(lambda k: gh.init_params(cfg, pcfg, k),
                            jax.random.PRNGKey(0))
    specs = gh.param_specs(cfg, pcfg)
    mspecs = gh.moment_specs(shapes, pcfg, specs)

    def placed(tree, spec_tree):
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
            tree, spec_tree)
    moments = placed(shapes, mspecs)
    opt = {"m": moments, "v": moments,
           "step": jax.ShapeDtypeStruct((), jnp.int32,
                                        sharding=NamedSharding(mesh, P()))}
    # the batch as the loop feeds it: uncommitted, so without a sharding
    ids = jax.ShapeDtypeStruct((4, 2048), jnp.int32)
    step = gh.build_train_step(cfg, pcfg, mesh, state_specs=(specs, mspecs))
    # a compile for a described chip cannot be read back from the cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        # _attend asks the backend whether to take the Pallas kernel
        with mock.patch.object(jax, "default_backend", lambda: "tpu"), mesh:
            return step.lower(placed(shapes, specs), opt,
                              (ids, ids)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        cc.reset_cache()


def _bytes(compiled):
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes + mem.temp_size_in_bytes


@pytest.fixture(scope="module")
def program():
    """(compiled text, bytes a device) of the step, compiled once."""
    try:
        compiled = compile_cell_step()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return compiled.as_text(), _bytes(compiled)


@functools.lru_cache(maxsize=2)
def _computations(text):
    """{name: lines} of the module's computations, the entry as ENTRY."""
    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            bodies[name] = []
        elif name is not None:
            bodies[name].append(line)
    return bodies


def _lines(text, op):
    """The entry computation's ``op`` instructions: what a step runs, not
    what a fusion's body spells out."""
    return [line for line in _computations(text)["ENTRY"]
            if f" {op}(" in line]


def _gathers(text, shape):
    """All-gathers to ``shape`` that a step runs: the synchronous ones of
    the entry computation, and the asynchronous ones by their start (on
    the TPU an async collective is a chain of fusions over one buffer,
    ``async-collective-start.N`` .. ``async-collective-done.N``, each of
    which shows the all-gather in its body: lines are not gathers)."""
    made = re.compile(rf"= {re.escape(shape)}\S* all-gather\(")
    bodies = _computations(text)
    count = 0
    for line in bodies["ENTRY"]:
        if made.search(line):
            count += 1
        elif re.match(r"\s*%async-collective-start[.\d]* = ", line):
            body = bodies.get(re.search(r"calls=%([\w.\-]+)", line).group(1))
            count += any(made.search(b) for b in body or ())
    return count


@pytest.mark.parametrize("leaf, local, shard", [
    ("fc1_w", "bf16[4096,8192]", (2048, 8192)),
    ("fc2_w", "bf16[8192,4096]", (8192, 2048)),
    ("proj_w", "bf16[2048,4096]", (2048, 2048)),
    ("qkv_w", "bf16[4096,6144]", (2048, 6144)),
])
def test_a_layers_gradient_is_not_all_reduced_over_dp(program, leaf, local,
                                                      shard):
    text, _ = program
    over_dp = [line for line in _lines(text, "all-reduce")
               if any(g in line for g in DP_GROUPS)
               and local in line.split(" all-reduce(")[0]]
    assert not over_dp, over_dp[0][:300]
    # nor is a reduced layer sent on to the rank that owns it
    assert not [line for line in _lines(text, "collective-permute-start")
                if "concatenate" in line
                and local.replace("[", "[1,") in line]
    if leaf == "qkv_w":
        # its gradient is partial over tp too (the product runs over the
        # sequence shards: ROADMAP Speed 1, the qkv layout), and XLA sums
        # such a leaf over the whole mesh in one all-reduce of the
        # untiled width, as fast on the chip as the parent's two steps
        mesh_wide = [line for line in _lines(text, "all-reduce")
                     if "[1,4]<=[4]" in line and "bf16[4096,12288]" in line]
        assert len(mesh_wide) >= LAYERS - 1
        return
    # the tp-local gradient goes into a reduce-scatter a layer; the shard
    # may carry a few rows of bias gradients that XLA reduces with it
    rows, cols = shard
    outs = re.findall(
        rf"^%all-reduce-scatter[.\d]* \(input[.\d]*: {re.escape(local)}\)"
        r" -> bf16\[(\d+),(\d+)\]", text, flags=re.M)
    assert len([1 for r, c in outs if rows <= int(r) <= rows + 128
                and int(c) == cols]) == LAYERS, outs


def test_no_weight_shaped_layer_slice_is_permuted(program):
    text, _ = program
    sent = [line for line in _lines(text, "collective-permute-start")
            if "concatenate" in line
            and re.search(r"bf16\[1,\d{4,},\d{4,}\]", line)]
    assert not sent, sent[0][:300]


def test_gathers_and_memory_against_the_parent(program):
    text, nbytes = program
    assert _gathers(text, "bf16[2,2048,4096]") <= PARENT["ag_2_2048_4096"]
    assert _gathers(text, "bf16[4096,12288]") <= PARENT["ag_4096_12288"]
    assert nbytes <= PARENT["bytes"]


@pytest.mark.parametrize("stacked", [
    "bf16[4,4096,6144]", "bf16[4,4096,8192]", "bf16[4,8192,4096]",
    "bf16[4,2048,4096]"], ids=["qkv_w", "fc1_w", "fc2_w", "proj_w"])
def test_new_parameters_are_gathered_once_a_leaf(program, stacked):
    assert _gathers(program[0], stacked) == 1


def test_dp1_step_lowers_as_without_zero1():
    """``gpt3-1.3b``'s layout (one device): no moment is dp-sharded, no
    constraint is placed, the step is zero1=False's to the byte."""
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=2, max_seq_len=64)
    ids = jnp.zeros((2, 64), jnp.int32)

    def lowered(zero1):
        pcfg = _pcfg(dp=1, tp=1, sp=False, scan_unroll=2, zero1=zero1)
        mesh, params, opt, step = gh.setup(cfg, pcfg,
                                           devices=jax.devices()[:1])
        with mesh:
            return step.lower(params, opt, (ids, ids)).as_text()
    assert lowered(True) == lowered(False)


if __name__ == "__main__":
    import sys
    done = compile_cell_step(int(sys.argv[1]) if sys.argv[1:] else LAYERS)
    hlo = done.as_text()
    print({"bytes": _bytes(done),
           "code_bytes": done.memory_analysis().generated_code_size_in_bytes,
           **{shape: _gathers(hlo, shape)
              for shape in ("bf16[4096,12288]", "bf16[2,2048,4096]")},
           "all-reduce over dp": len([x for x in _lines(hlo, "all-reduce")
                                      if any(g in x for g in DP_GROUPS)]),
           "all-reduce-scatter fusions": len(re.findall(
               r"^%all-reduce-scatter", hlo, flags=re.M))})
