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

Since PR 33 ``shard_params`` lays ``qkv_w`` [L, h, 3, h] with tp on the last
dim, so a tp rank's columns are its own heads of q, k and v: the step has
no collective left that moved a layer's product or its weight between the
tp ranks, and ``qkv_w``'s gradient is a reduce-scatter over dp like its
siblings'. ``PARENT_QKV`` keeps what the same compile showed at 89da449.

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
#: the same compile at 89da449 (PR 33's parent), 4 layers: what the flat
#: [L, h, 3h] layout of qkv_w cost a step. Permutes of a layer's product to
#: bf16[2,2048,4096] (4 a layer: ``block/split``, forward and rematerialised),
#: all-to-alls to bf16[2,2,1024,2048] in the backward pass (3 a layer),
#: gathers of the whole bf16[4096,12288] weight (1 a layer; a thing of the
#: past, as ``PARENT["ag_4096_12288"]`` is), all-reduces of its untiled
#: gradient over the whole mesh, the sequence gathers (7 a layer, where
#: Megatron's sp has 8: the eighth went by all-to-all), bytes a device (at
#: 16 layers 14,978,663,936, and 14,835,507,200 with the new layout)
PARENT_QKV = {"permute_2_2048_4096": 16, "a2a_2_2_1024_2048": 12,
              "ag_4096_12288": 4, "ar_4096_12288": 3,
              "ag_2_2048_4096": 28, "bytes": 4_825_157_632}


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
    def laid_out(key):
        # the tree shard_params places: qkv_w [L, h, 3, h], qkv_b [L, 3, h]
        params = gh.init_params(cfg, pcfg, key)
        return {**params,
                "blocks": gh._qkv_per_matrix(params["blocks"], cfg)}
    shapes = jax.eval_shape(laid_out, jax.random.PRNGKey(0))
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
    # a tp rank's [h, 3, h/tp] of qkv_w: over dp, into the owner's half of h
    ("qkv_w", "bf16[4096,3,2048]", (2048, "3,2048")),
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
    # the tp-local gradient goes into a reduce-scatter a layer; the shard
    # may carry a few rows of bias gradients that XLA reduces with it
    rows, cols = shard
    outs = re.findall(
        rf"^%all-reduce-scatter[.\d]* \(input[.\d]*: {re.escape(local)}\)"
        r" -> bf16\[(\d+),([\d,]+)\]", text, flags=re.M)
    assert len([1 for r, c in outs if rows <= int(r) <= rows + 128
                and c == str(cols)]) == LAYERS, outs


def test_no_weight_shaped_layer_slice_is_permuted(program):
    text, _ = program
    sent = [line for line in _lines(text, "collective-permute-start")
            if "concatenate" in line
            and re.search(r"bf16\[1,\d{4,},\d{4,}\]", line)]
    assert not sent, sent[0][:300]


def test_gathers_and_memory_against_the_parent(program):
    text, nbytes = program
    # Megatron's sp gathers its sequence shards 8 times a layer; the flat
    # qkv layout had 7 and moved the eighth's activations by all-to-all
    assert _gathers(text, "bf16[2,2048,4096]") == 8 * LAYERS
    assert _gathers(text, "bf16[4096,12288]") == 0
    assert nbytes <= PARENT["bytes"]


def _started(text, kind, shape):
    """``kind`` collectives to ``shape`` that a step runs: the entry
    computation's, synchronous or by their ``-start``."""
    from chip_smoke import _step_collectives
    return _step_collectives("\n".join(_computations(text)["ENTRY"]),
                             kinds=(kind,)).get(f"{kind} {shape}", 0)


@pytest.mark.parametrize("kind, shape, parent", [
    ("collective-permute", "bf16[2,2048,4096]", "permute_2_2048_4096"),
    ("all-to-all", "bf16[2,2,1024,2048]", "a2a_2_2_1024_2048"),
    ("all-gather", "bf16[4096,12288]", "ag_4096_12288"),
    ("all-reduce", "bf16[4096,12288]", "ar_4096_12288"),
])
def test_no_collective_repairs_the_qkv_layout(program, kind, shape, parent):
    """A tp rank holds the q, k and v columns of its own heads, which is
    what ``_attend``'s shard_map runs on: nothing is left to send."""
    text, _ = program
    assert _started(text, kind, shape) == 0 < PARENT_QKV[parent]
    if kind == "all-gather":
        assert _gathers(text, shape) == 0       # nor an asynchronous one
    # nor the same bytes under the new layout's shape
    assert _started(text, kind, "bf16[4096,3,4096]") == 0


def test_memory_against_the_parent_of_the_qkv_layout(program):
    """At 4 layers the schedule XLA picks holds 1.05 % more than 89da449's
    (4,875,708,928 against 4,825,157,632 bytes); at the cell's 16 layers it
    holds 143 MB less (``PARENT_QKV``'s comment; PERF.md, section 4)."""
    assert program[1] <= PARENT_QKV["bytes"] * 1.02


@pytest.mark.parametrize("stacked", [
    "bf16[4,4096,3,2048]", "bf16[4,4096,8192]", "bf16[4,8192,4096]",
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
           **{f"{kind} {shape}": _started(hlo, kind, shape)
              for kind, shape in (
                  ("collective-permute", "bf16[2,2048,4096]"),
                  ("all-to-all", "bf16[2,2,1024,2048]"),
                  ("all-reduce", "bf16[4096,12288]"))},
           "all-reduce over dp": len([x for x in _lines(hlo, "all-reduce")
                                      if any(g in x for g in DP_GROUPS)]),
           "all-reduce-scatter fusions": len(re.findall(
               r"^%all-reduce-scatter", hlo, flags=re.M))})
