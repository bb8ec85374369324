"""Lint-style gate: every metric the framework emits is declared in
the single canonical catalog (observability/catalog.py), and the
catalog carries no dead names — so dashboards, the Prometheus scrape
endpoint and the benchmark's readers can never silently drift from
the emission sites (ISSUE 13 satellite).

Pure AST walk over paddle_tpu/ — no imports of the walked modules, no
jax; runs in well under a second."""
import ast
import pathlib

import paddle_tpu
from paddle_tpu.observability import catalog

#: method/function names whose first string-literal argument is a
#: metric name: the registry entry points plus known thin wrappers
#: (flash_attention's trace-time ``_count``; auto_tuner's ``_count``)
_EMITTERS = {"counter", "gauge", "histogram", "_count"}

PKG_ROOT = pathlib.Path(paddle_tpu.__file__).parent


def _emitted_names():
    """{metric name: [file:line, ...]} for every walker-visible
    emission site in the package."""
    out = {}
    for path in sorted(PKG_ROOT.rglob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            name = (fn.attr if isinstance(fn, ast.Attribute)
                    else fn.id if isinstance(fn, ast.Name) else None)
            if name not in _EMITTERS:
                continue
            if not (node.args and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue  # dynamic name: the wrapper's own def site
            rel = path.relative_to(PKG_ROOT.parent)
            out.setdefault(node.args[0].value, []).append(
                f"{rel}:{node.lineno}")
    return out


def test_every_emitted_metric_is_cataloged():
    emitted = _emitted_names()
    assert emitted, "walker found no emission sites — it is broken"
    missing = {n: sites for n, sites in emitted.items()
               if n not in catalog.CATALOG}
    assert not missing, (
        "metric names emitted but missing from "
        "observability/catalog.py (add them there — single canonical "
        f"home): {missing}")


def test_catalog_has_no_dead_names():
    emitted = set(_emitted_names())
    dead = set(catalog.CATALOG) - emitted - catalog.internal_names()
    assert not dead, (
        "catalog entries with no emission site left in the code "
        f"(remove or mark internal=True): {sorted(dead)}")


def test_internal_names_really_registered():
    """internal=True entries bypass the walker, so pin their
    registration mechanics directly: the cardinality-overflow counter
    must exist under its cataloged name once a drop happens."""
    import paddle_tpu.observability as obs
    obs.enable()
    reg = obs.REGISTRY
    old_cap = reg.max_series_per_name
    reg.max_series_per_name = 2
    try:
        before = obs.counter("metrics.dropped_series").value
        for i in range(4):
            reg.counter("t.catalog_overflow", i=str(i)).inc()
        assert obs.counter("metrics.dropped_series").value == before + 2
    finally:
        reg.max_series_per_name = old_cap
    assert "metrics.dropped_series" in catalog.internal_names()


def test_serving_robustness_counters_cataloged():
    """The ISSUE 14 outcome counters are the perf-gate's
    'shed, never collapse' vocabulary: pin that each exists in the
    catalog with the right kind AND has a real emission site in the
    serving layer (not just a catalog entry someone forgot to wire)."""
    emitted = _emitted_names()
    expected = {
        "serving.rejected": "counter",
        "serving.timed_out": "counter",
        "serving.cancelled": "counter",
        "serving.step_retries": "counter",
        "serving.quarantined": "counter",
        "serving.degraded": "gauge",
    }
    for name, kind in expected.items():
        assert name in catalog.CATALOG, name
        assert catalog.CATALOG[name]["kind"] == kind, name
        sites = emitted.get(name, [])
        assert any("inference" in s for s in sites), (name, sites)


def test_training_robustness_counters_cataloged():
    """The ISSUE 15 train.* names are the training fault-drill
    vocabulary: pin that each exists in the catalog with the right
    kind AND has a real emission site in the layer that owns it."""
    emitted = _emitted_names()
    expected = {
        "train.nan_steps": ("counter", "paddle_tpu/training"),
        "train.skipped_steps": ("counter", "paddle_tpu/training"),
        "train.checkpoint_saves": ("counter", "paddle_tpu/training"),
        "train.hang_aborts": ("counter", "watchdog"),
        "train.straggler_ranks": ("gauge", "watchdog"),
        "train.restarts": ("counter", "elastic"),
        "train.preemptions": ("counter", "hapi"),
    }
    for name, (kind, where) in expected.items():
        assert name in catalog.CATALOG, name
        assert catalog.CATALOG[name]["kind"] == kind, name
        sites = emitted.get(name, [])
        assert any(where in s for s in sites), (name, sites)


def test_zero1_moment_shard_counter_cataloged():
    """ISSUE 31: where ZeRO-1 put dp, once a moment leaf, is a counter of
    the train engine with the one label the chip smoke reads."""
    entry = catalog.CATALOG["zero1.moment_shard"]
    assert entry["kind"] == "counter" and entry["labels"] == ("dim",)
    sites = _emitted_names().get("zero1.moment_shard", [])
    assert sites and all("models/gpt_hybrid.py" in s for s in sites), sites


def test_catalog_entries_well_formed():
    for name, d in catalog.CATALOG.items():
        assert d["kind"] in ("counter", "gauge", "histogram"), name
        assert d["help"], f"{name}: empty help string"
        assert isinstance(d["labels"], tuple), name
        # the check() helper gives a pointed error for unknown names
    try:
        catalog.check("no.such.metric")
    except KeyError as e:
        assert "catalog.py" in str(e)
    else:
        raise AssertionError("catalog.check accepted an unknown name")
