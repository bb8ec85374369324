"""Runtime attention-kernel autotune: measure-and-cache dispatch.

Reference being re-designed: phi/kernels/autotune/{auto_tune_base.h,
cache.cc,switch_autotune.cc} — run each candidate kernel once with a
GPU timer, cache the winner keyed by shape, re-use thereafter.

TPU-native version: the candidates are the three monolithic in-tree
Pallas attention kernels, the q×kv-blocked flash kernel (one candidate
per (bq, bkv) block-size variant — `blocked_bq512_bkv512` etc., so
block sizes are autotuned along with the kernel choice), the jax
library flash kernel, and plain XLA attention. A measurement times
fwd+bwd (the kernels live inside
training steps) under jit, closed by ``block_until_ready``. A candidate
the compiler refuses leaves the race, but loudly: its exception text is
kept in the table entry (``errors``) and ticks
``attn.autotune_candidate_errors{kernel=}``. Winners are
cached per (device_kind, B, H, S, Skv, D, dtype, causal) in memory and
persisted as JSON so later processes on the same device kind skip the
measurement. Under tracing (shapes are tracers at dispatch time inside
jit) the table answers; with no entry the static chain
(flash_attention.flash_attention_maybe docstring) decides, so
cold-trace behavior is exactly the hand-tuned round-1 dispatch.
"""
from __future__ import annotations

import json
import math
import os
import re
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.core.flags import define_flag, get_flag

define_flag("FLAGS_attn_autotune", True,
            "measure-and-cache attention kernel choice on the first "
            "eager call per shape (trace-time dispatch only consults "
            "the cached table)")

#: candidate name -> runner(q, k, v, causal, scale) in [B,S,H,D] layout;
#: populated lazily to keep kernel imports off the module-import path
_RUNNERS = None

_table: Optional[Dict[str, dict]] = None


def _bhsd(run):
    """[B,S,H,D] entry -> [B,H,S,D] kernel-layout runner."""
    def wrapped(q, k, v, causal, scale):
        qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
        return jnp.swapaxes(run(qt, kt, vt, causal, scale), 1, 2)
    return wrapped


def _cache_path() -> str:
    base = os.environ.get("PADDLE_TPU_CACHE_DIR")
    if base is None:
        base = os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))), ".cache")
    return os.path.join(base, "attn_autotune.json")


def _read_disk_table(path: str) -> Dict[str, dict]:
    """Best-effort read; a corrupted / partially written / wrong-schema
    file degrades to {} (the static chain) instead of raising."""
    try:
        with open(path) as f:
            tab = json.load(f)
    except (OSError, ValueError):
        return {}
    return tab if isinstance(tab, dict) else {}


def _load_table() -> Dict[str, dict]:
    global _table
    if _table is None:
        _table = _read_disk_table(_cache_path())
    return _table


def _save_table() -> None:
    global _table
    path = _cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        # merge-then-replace: re-read the file so winners measured by a
        # concurrent process since our load are kept (our entries win
        # on key collision), and write via temp file + os.replace so a
        # concurrent reader can never observe a partial write
        merged = _read_disk_table(path)
        merged.update(_table)
        _table = merged
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(merged, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass                        # read-only FS: in-memory cache only


def _device_kind() -> str:
    return jax.devices()[0].device_kind.replace(" ", "_")


def _key(bshd: Tuple[int, int, int, int], skv: int, dtype,
         causal: bool) -> str:
    b, s, h, d = bshd
    return (f"{_device_kind()}|B{b}S{s}H{h}D{d}Skv{skv}|"
            f"{jnp.dtype(dtype).name}|causal={bool(causal)}")


def _runners():
    global _RUNNERS
    if _RUNNERS is not None:
        return _RUNNERS
    from paddle_tpu.ops.pallas import causal_attention as cak
    from paddle_tpu.ops.pallas import simple_attention as sa
    from paddle_tpu.ops.pallas import simple_attention2 as sa2
    from paddle_tpu.ops.pallas import flash_attention as fa

    def _xla(q, k, v, causal, scale):
        d = q.shape[-1]
        sm = scale if scale is not None else 1.0 / math.sqrt(d)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * sm
        if causal:
            sq, sk = q.shape[1], k.shape[1]
            mask = jnp.tril(jnp.ones((sq, sk), bool), sk - sq)
            logits = jnp.where(mask, logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    _RUNNERS = {
        "simple": _bhsd(lambda q, k, v, c, s: sa.attention_bhsd(
            q, k, v, causal=c, scale=s)),
        "causal_skip": _bhsd(lambda q, k, v, c, s: cak.attention_bhsd(
            q, k, v, causal=c, scale=s)),
        "qblock": _bhsd(lambda q, k, v, c, s: sa2.attention_bhsd(
            q, k, v, causal=c, scale=s)),
        "library_flash": fa.flash_attention,
        "xla": _xla,
    }
    return _RUNNERS


_BLOCKED_RE = re.compile(r"^blocked_bq(\d+)_bkv(\d+)$")


def blocked_name(bq: int, bkv: int) -> str:
    return f"blocked_bq{bq}_bkv{bkv}"


def _resolve(name: str):
    """Runner for a candidate name; blocked variants carry their block
    sizes in the name so the winner cache pins (kernel, bq, bkv)."""
    m = _BLOCKED_RE.match(name)
    if m is None:
        return _runners()[name]
    bq, bkv = int(m.group(1)), int(m.group(2))
    from paddle_tpu.ops.pallas import blocked_flash as bf
    return _bhsd(lambda q, k, v, c, s: bf.attention_bhsd(
        q, k, v, causal=c, scale=s, block_q=bq, block_kv=bkv))


def candidates(bshd, skv, dtype, causal) -> List[str]:
    """Kernels whose shape gates accept this problem ([B,S,H,D])."""
    from paddle_tpu.ops.pallas import blocked_flash as bf
    from paddle_tpu.ops.pallas import causal_attention as cak
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import simple_attention as sa
    from paddle_tpu.ops.pallas import simple_attention2 as sa2
    b, s, h, d = bshd
    bhsd = (b, h, s, d)
    out = []
    if s == skv:
        if sa.supported(bhsd, dtype):
            out.append("simple")
        if causal and cak.supported(bhsd, dtype):
            out.append("causal_skip")
        if sa2.supported(bhsd, dtype):
            out.append("qblock")
    if bf.supported(bhsd, skv, dtype, causal):
        out.extend(blocked_name(bq, bkv)
                   for bq, bkv in bf.block_candidates(s, skv))
    if fa.supported_shape(bshd, skv, dtype):
        out.append("library_flash")
    out.append("xla")
    return out


def _time_candidate(name: str, q, k, v, causal, scale,
                    reps: int = 3) -> float:
    """fwd+bwd wall time per rep; a kernel that fails raises."""
    run = _resolve(name)

    def fb(q, k, v):
        out, vjp = jax.vjp(lambda a, b, c: run(a, b, c, causal, scale),
                           q, k, v)
        return vjp(jnp.ones_like(out))

    fb = jax.jit(fb)
    jax.block_until_ready(fb(q, k, v))
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fb(q, k, v)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / reps


def measure(bshd, skv, dtype, causal, scale=None) -> str:
    """Benchmark all shape-feasible candidates on random data, record
    the winner in the (persisted) table, return its name."""
    from paddle_tpu.ops.pallas.flash_attention import _count
    tab = _load_table()
    key = _key(bshd, skv, dtype, causal)
    hit = lookup(bshd, skv, dtype, causal)   # schema-validated; a
    if hit is not None:                      # wrong-schema entry gets
        return hit                           # re-measured + rewritten
    b, s, h, d = bshd
    rng = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (b, s, h, d), dtype)
    k = jax.random.normal(kk, (b, skv, h, d), dtype)
    v = jax.random.normal(kv, (b, skv, h, d), dtype)
    timings, errors = {}, {}
    for name in candidates(bshd, skv, dtype, causal):
        try:
            timings[name] = _time_candidate(name, q, k, v, causal, scale)
        except Exception as e:  # noqa: BLE001 — the candidate leaves
            # the race; the refusal is recorded, never dropped
            errors[name] = f"{type(e).__name__}: {e}"[:2000]
            _count("attn.autotune_candidate_errors", kernel=name)
    if not timings:
        raise RuntimeError(
            f"attention autotune: every candidate failed for {key}: "
            f"{errors}")
    winner = min(timings, key=timings.get)
    tab[key] = {"winner": winner,
                "timings_ms": {n: round(t * 1e3, 4)
                               for n, t in timings.items()}}
    if errors:
        tab[key]["errors"] = errors
    _save_table()
    return winner


def lookup(bshd, skv, dtype, causal) -> Optional[str]:
    ent = _load_table().get(_key(bshd, skv, dtype, causal))
    # schema-validate: a hand-edited or partially merged entry must
    # degrade to the static chain, not crash dispatch
    if not isinstance(ent, dict) or not isinstance(
            ent.get("winner"), str):
        return None
    return ent["winner"]


def decide(q, k, causal) -> Optional[str]:
    """Dispatch decision for concrete or traced q/k ([B,S,H,D]).

    Concrete arrays with autotune enabled: measure (once) and answer
    from the table. Traced: table lookup only. None means "use the
    static chain" — also the escape hatch: disabling the flag bypasses
    the table entirely, restoring the hand-tuned chain.
    """
    if not get_flag("FLAGS_attn_autotune"):
        return None
    if get_flag("FLAGS_deterministic"):
        # deterministic mode: no measurement-dependent kernel choice
        return None
    bshd = tuple(q.shape)
    skv = k.shape[1]
    hit = lookup(bshd, skv, q.dtype, causal)
    if hit is not None:
        return hit
    if isinstance(q, jax.core.Tracer):
        return None
    if jax.default_backend() != "tpu":
        return None                 # measuring CPU pallas is meaningless
    if jax.process_count() > 1:
        # multi-process SPMD: per-rank measurement could pick
        # different kernels per rank; keep the deterministic chain
        return None
    return measure(bshd, skv, q.dtype, causal)


def run(name: str, q, k, v, causal, scale):
    return _resolve(name)(q, k, v, causal, scale)
