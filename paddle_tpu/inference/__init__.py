"""paddle.inference equivalent (reference: AnalysisPredictor,
fluid/inference/api/analysis_predictor.h:105 — config + predictor with
zero-copy tensors, pass pipelines, TensorRT bridges).

TPU-native: the reference's "analysis + IR passes + engine" stack IS
XLA — graph capture is jax tracing, fusion/memory planning is the XLA
pipeline, the engine is a compiled executable. What remains to build
(and is built here) are the parts XLA does NOT own:

  * precision passes — enable_low_precision_inference casts served
    weights + compute to bf16/fp16 (the reference's mixed-precision
    pass); enable_int8_weight_only quantizes weights to int8 with
    per-channel scales and dequantizes at the matmul edge (the PTQ
    weight-only path; halves HBM for the weights)
  * shape bucketing — enable_shape_bucketing pads the batch dim to a
    fixed bucket ladder so arbitrary request sizes hit a BOUNDED set
    of XLA executables (the serving analog of TensorRT's optimization
    profiles)
  * zero-copy IO — handles adopt existing device arrays without a
    host round trip (share_external_data)
  * async execution — run_async returns immediately (XLA dispatch is
    async); the future's .get() materializes
  * warmup + execution stats — precompile the bucket ladder, count
    compiles/hits/latency (the reference's profile summary)
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor


class Config:
    def __init__(self, model_path: Optional[str] = None,
                 params_path: Optional[str] = None):
        self.model_path = model_path
        self.params_path = params_path
        self._layer = None
        self._donate = True
        self._precision = None          # None | bf16/fp16 jnp dtype
        self._int8_weights = False
        self._buckets: Optional[List[int]] = None
        self._decode: Optional[dict] = None

    # ---- reference-config surface (XLA-internal knobs are no-ops) ----
    def enable_use_gpu(self, *a, **k):
        pass

    def enable_tpu(self, *a, **k):
        pass

    def disable_gpu(self):
        pass

    def switch_ir_optim(self, flag=True):
        pass

    def enable_memory_optim(self, flag=True):
        pass

    def set_cpu_math_library_num_threads(self, n):
        pass

    def enable_tensorrt_engine(self, *a, **k):
        raise NotImplementedError("TensorRT has no TPU analog; XLA "
                                  "compiles the graph directly")

    # ---- real serving passes ----------------------------------------
    def enable_low_precision_inference(self, dtype="bfloat16"):
        """Mixed-precision pass: serve weights + compute in bf16/fp16
        (reference convert_to_mixed_precision / the gpu fp16 pass)."""
        from paddle_tpu.core import dtype as dtype_mod
        self._precision = dtype_mod.convert_dtype(dtype)
        return self

    def enable_int8_weight_only(self, flag=True):
        """PTQ weight-only int8: per-output-channel symmetric scales.
        The served weights are quantize-dequantized in place (exact
        accuracy parity with an int8 deployment) and the int8 payload
        + scales are kept on each parameter (`_int8_payload`) for an
        int8-native export — HBM savings come from shipping that
        payload, not from this in-memory emulation."""
        self._int8_weights = bool(flag)
        return self

    def enable_decode(self, max_length: int, prefill_buckets=None,
                      temperature=0.0, top_p=None, eos_token_id=None):
        """Serving decode config: fixed-capacity KV cache of
        `max_length`, prefill compiled per bucket, one compiled decode
        step (see inference/decode.py). Enables Predictor.generate."""
        self._decode = dict(max_length=int(max_length),
                            prefill_buckets=prefill_buckets,
                            temperature=temperature, top_p=top_p,
                            eos_token_id=eos_token_id)
        return self

    def enable_shape_bucketing(self, buckets: Sequence[int]):
        """Pad the leading (batch) dim up to the nearest bucket so any
        request size compiles at most len(buckets) executables."""
        self._buckets = sorted(int(b) for b in buckets)
        return self

    def set_model(self, model_path, params_path=None):
        self.model_path = model_path
        self.params_path = params_path

    def set_layer(self, layer):
        """Directly serve an in-memory Layer (fast path)."""
        self._layer = layer


def _quantize_int8(arr, channel_axis):
    """Per-channel symmetric int8 quantization; scales from the single
    quantization-module observer (one home for the scale math)."""
    from paddle_tpu.core.tensor import Tensor as _T
    from paddle_tpu.quantization import GroupWiseWeightObserver
    a = np.asarray(arr, np.float32)
    obs = GroupWiseWeightObserver(channel_axis=channel_axis)
    obs.observe(_T(a))
    ax = channel_axis % a.ndim
    shape = [1] * a.ndim
    shape[ax] = -1
    scale = np.maximum(np.asarray(obs.scale(), np.float32),
                       1e-8).reshape(shape)
    q = np.clip(np.round(a / scale), -127, 127).astype(np.int8)
    return q, scale


class Predictor:
    def __init__(self, config: Config):
        self._config = config
        self._layer = config._layer
        if self._layer is None and config.model_path:
            # serve a jit.save artifact: <path>.pdmodel is a serialized
            # jax.export program, loaded as a TranslatedLayer
            import os
            path = config.model_path
            for suffix in (".pdmodel", ".json"):
                if path.endswith(suffix):
                    path = path[:-len(suffix)]
            if os.path.exists(path + ".pdmodel"):
                self._layer = paddle.jit.load(path)
        if self._layer is None:
            raise NotImplementedError(
                "the predictor needs a model: pass Config(model_path) "
                "pointing at a paddle_tpu.jit.save artifact, or use "
                "Config.set_layer(layer) (+ layer.set_state_dict("
                "paddle.load(...)) for file-based weights)")
        self._apply_passes()
        self._inputs: Dict[str, Tensor] = {}
        self._compiled = None
        self._last_out: Optional[Tensor] = None
        self.stats = {"runs": 0, "bucket_pad_total": 0,
                      "last_latency_ms": None, "warmup_shapes": []}

    # ---- precision / quantization passes over the served weights ----
    def _apply_passes(self):
        from paddle_tpu.jit import TranslatedLayer
        cfg = self._config
        if isinstance(self._layer, TranslatedLayer):
            return                      # weights frozen in the program
        if cfg._int8_weights:
            self._int8_rewrite()
        if cfg._precision is not None:
            # composes with int8: QDQ'd weights are then SERVED in the
            # low precision (int8-emulated values, bf16 compute)
            for _, p in self._layer.named_parameters():
                if jnp.issubdtype(p._data.dtype, jnp.floating):
                    p._assign_array(p._data.astype(cfg._precision))
            for _, b in self._layer.named_buffers():
                if jnp.issubdtype(b._data.dtype, jnp.floating):
                    b._assign_array(b._data.astype(cfg._precision))

    def _int8_rewrite(self):
        """Quantize-dequantize every >=2-D float parameter in place
        (int8 deployment numerics) and stash the (int8, scale) payload
        on the parameter for int8-native export. Channel convention:
        last dim for matrices (Linear [in, out]), dim 0 for conv
        weights ([out, in, k...])."""
        for _, p in self._layer.named_parameters():
            a = p._data
            if a.ndim >= 2 and jnp.issubdtype(a.dtype, jnp.floating):
                ax = -1 if a.ndim == 2 else 0
                q, scale = _quantize_int8(a, ax)
                deq = jnp.asarray(q, jnp.int8)
                sc = jnp.asarray(scale)
                p._assign_array((deq.astype(jnp.float32) * sc
                                 ).astype(a.dtype))
                p._int8_payload = (deq, sc)   # int8-native export

    # ---- IO handles --------------------------------------------------
    def get_input_names(self):
        return list(self._inputs) or ["x"]

    def get_input_handle(self, name):
        t = self._inputs.setdefault(name, paddle.zeros([1]))
        return _Handle(t)

    def get_output_names(self):
        return ["out"]

    def get_output_handle(self, name):
        # late-binding: reads the output of the most recent run()
        return _OutputHandle(self)

    # ---- execution ---------------------------------------------------
    def _bucketize(self, args):
        """Pad the BATCH dim (the first input's leading dim) up to the
        bucket ladder. Only inputs sharing that batch size are padded —
        side inputs (lookup tables, per-position tensors) pass through
        untouched; outputs whose leading dim is the padded batch are
        trimmed back. Returns (args, true_batch, padded_batch);
        (args, None, None) means no padding happened (None, not 0 —
        a true batch of 0 pads and must still trim)."""
        buckets = self._config._buckets
        if not buckets or not args or args[0]._data.ndim == 0:
            return args, None, None
        batch = args[0].shape[0]
        tgt = next((k for k in buckets if k >= batch), buckets[-1])
        if tgt <= batch:
            return args, None, None
        out = []
        for a in args:
            if a.shape[0] == batch:
                pad = [(0, tgt - batch)] + [(0, 0)] * (a._data.ndim - 1)
                out.append(Tensor._wrap(jnp.pad(a._data, pad), True))
            else:
                out.append(a)
        return out, batch, tgt

    def _batch_output_flags(self, args):
        """Per-output batch relationship, probed with jax.eval_shape at
        two batch sizes (no execution, no compile):
          True  — dim0 IS the batch (safe to pad + trim)
          False — dim0 is batch-independent (pass through)
          "affine" — dim0 depends on the batch but is not equal to it
                     (e.g. 2*B): padding cannot be undone by trimming,
                     so bucketing must be skipped entirely
        None when the model cannot be abstractly evaluated."""
        # normalize the batch dim out of the key: flags depend only on
        # WHICH dims track the batch, so arbitrary request sizes reuse
        # one cache entry instead of re-probing per novel batch size
        batch0 = args[0].shape[0] if args and args[0]._data.ndim else None
        key = tuple(
            (("B",) + tuple(a._data.shape[1:])
             if a._data.ndim and a.shape[0] == batch0
             else tuple(a._data.shape), a._data.dtype.name)
            for a in args)
        if key in getattr(self, "_flag_cache", {}):
            return self._flag_cache[key]
        if not hasattr(self, "_flag_cache"):
            self._flag_cache = {}
        batch = args[0].shape[0]

        def shapes_at(b):
            specs = []
            for a in args:
                shp = list(a._data.shape)
                if shp and shp[0] == batch:
                    shp[0] = b
                specs.append(jax.ShapeDtypeStruct(tuple(shp),
                                                  a._data.dtype))

            def fn(*xs):
                with paddle.no_grad():
                    o = self._layer(*[Tensor._wrap(x, True)
                                      for x in xs])
                o = [o] if isinstance(o, Tensor) else list(o)
                return [t._data for t in o]
            return jax.eval_shape(fn, *specs)

        try:
            b1, b2 = max(batch, 1), max(batch, 1) + 1
            s1 = shapes_at(b1)
            s2 = shapes_at(b2)
            flags = []
            for a, b in zip(s1, s2):
                d1 = a.shape[0] if a.shape else None
                d2 = b.shape[0] if b.shape else None
                if d1 == d2:
                    flags.append(False)
                elif (d1, d2) == (b1, b2):
                    flags.append(True)
                else:
                    flags.append("affine")
        except Exception:
            flags = None                # fall back to the heuristic
        self._flag_cache[key] = flags
        return flags

    def _ensure_compiled(self):
        if self._compiled is None:
            from paddle_tpu.jit import TranslatedLayer
            self._layer.eval()
            if isinstance(self._layer, TranslatedLayer):
                self._compiled = self._layer
            else:
                self._compiled = paddle.jit.to_static(
                    lambda *xs: self._layer(*xs), objs=[self._layer],
                    donate=False)

    def warmup(self, shapes: Sequence[Sequence[int]],
               dtype="float32"):
        """Precompile the executable ladder for the given input shapes
        (serving cold-start elimination; with bucketing, pass one shape
        per bucket)."""
        from paddle_tpu.core import dtype as dtype_mod
        d = dtype_mod.convert_dtype(dtype)
        for shape in shapes:
            x = Tensor._wrap(jnp.zeros(tuple(shape), d), True)
            self.run([x])
            self.stats["warmup_shapes"].append(tuple(shape))
        return self

    def run(self, inputs: Optional[List[Tensor]] = None):
        outs = self._run_impl(inputs, block=True)
        self._last_out = outs[0]
        return outs

    def _run_impl(self, inputs, block, record=True):
        args = inputs if inputs is not None else \
            list(self._inputs.values())
        args = [a if isinstance(a, Tensor) else paddle.to_tensor(a)
                for a in args]
        from paddle_tpu.jit import TranslatedLayer
        if self._config._precision is not None and not isinstance(
                self._layer, TranslatedLayer):
            # TranslatedLayer programs have frozen f32 avals — the
            # precision pass does not apply to them
            args = [Tensor._wrap(a._data.astype(self._config._precision),
                                 True)
                    if jnp.issubdtype(a._data.dtype, jnp.floating)
                    else a for a in args]
        buckets = self._config._buckets
        flags = self._batch_output_flags(args) if buckets and args \
            else None
        # any batch-dependent-but-not-batch output (dim0 = 2B etc.)
        # cannot be padded-and-trimmed NOR chunked: run unbucketed.
        # A failed probe (flags None) also skips bucketing: without
        # per-output knowledge, trimming would have to guess which
        # outputs track the batch.
        bucketable = (not buckets or not args) if flags is None else \
            not any(f == "affine" for f in flags)
        if buckets and args and bucketable \
                and args[0].shape[0] > buckets[-1]:
            # bigger than the top bucket: chunk into top-bucket pieces
            # so the executable count stays bounded by the ladder.
            # Valid only when every output carries the batch — an
            # aggregate output cannot be reassembled from chunks.
            if flags is not None and all(f is True for f in flags):
                top = buckets[-1]
                batch = args[0].shape[0]
                t0 = time.perf_counter()
                pieces = []
                for lo in range(0, batch, top):
                    part = [Tensor._wrap(a._data[lo:lo + top], True)
                            if a.shape[0] == batch else a for a in args]
                    # dispatch chunks WITHOUT a per-chunk barrier so
                    # device work pipelines across them; inner calls
                    # don't touch stats — this is ONE user-visible run
                    pieces.append(self._run_impl(part, block=False,
                                                 record=False))
                outs = [Tensor._wrap(
                    jnp.concatenate([p[i]._data for p in pieces], 0),
                    True) for i in range(len(pieces[0]))]
                if block:
                    jax.block_until_ready([o._data for o in outs])
                if record:
                    self.stats["runs"] += 1
                    self.stats["last_latency_ms"] = \
                        (time.perf_counter() - t0) * 1e3
                return outs
        if bucketable:
            args, true_batch, padded = self._bucketize(args)
        else:
            true_batch = padded = None
        self._ensure_compiled()
        t0 = time.perf_counter()
        with paddle.no_grad():
            out = self._compiled(*args)
        outs = [out] if isinstance(out, Tensor) else list(out)
        if true_batch is not None:
            # trim ONLY the outputs whose leading dim actually tracks
            # the batch (probed abstractly — a [C] aggregate that
            # happens to equal the padded size must NOT be cut)
            outs = [Tensor._wrap(o._data[:true_batch], True)
                    if (flags[i] is True
                        if flags is not None and i < len(flags)
                        else o._data.ndim >= 1 and o.shape[0] == padded)
                    else o
                    for i, o in enumerate(outs)]
            self.stats["bucket_pad_total"] += 1
        if block:
            # latency means device completion, not async dispatch
            jax.block_until_ready([o._data for o in outs])
        if record:
            self.stats["runs"] += 1
            self.stats["last_latency_ms"] = \
                (time.perf_counter() - t0) * 1e3
        return outs

    def generate(self, input_ids, max_new_tokens=16, seed=0):
        """Serving generation over the fixed-capacity KV cache: needs
        Config.enable_decode and a layer implementing the
        init_cache/forward_with_cache contract (models/llama.py,
        models/gpt.py). ONE decode executable for all tokens."""
        if self._config._decode is None:
            raise RuntimeError("call Config.enable_decode(max_length) "
                               "before Predictor.generate")
        if not hasattr(self._layer, "forward_with_cache"):
            raise TypeError(
                "the served layer does not expose the decode contract "
                "(init_cache + forward_with_cache)")
        if getattr(self, "_decode_session", None) is None:
            from .decode import DecodeSession
            self._decode_session = DecodeSession(self._layer,
                                                 **self._config._decode)
        t0 = time.perf_counter()
        out = self._decode_session.generate(input_ids, max_new_tokens,
                                            seed=seed)
        self.stats["runs"] += 1
        self.stats["last_latency_ms"] = (time.perf_counter() - t0) * 1e3
        return out

    def run_async(self, inputs: Optional[List[Tensor]] = None):
        """Dispatch without blocking (XLA execution is async by
        design); the returned future materializes on .get()."""
        outs = self._run_impl(inputs, block=False)
        self._last_out = outs[0]
        return _Future(outs)

    def get_execution_stats(self):
        entry = self._compiled
        n_spec = 0
        if entry is not None and hasattr(entry, "specializations"):
            n_spec = sum(len(v) for v in
                         entry.specializations().values())
        return dict(self.stats, executables=n_spec)


class _Future:
    def __init__(self, outs):
        self._outs = outs

    def done(self):
        return True                     # dispatch already queued

    def get(self):
        for o in self._outs:
            jax.block_until_ready(o._data)
        return self._outs


class _OutputHandle:
    """Handle bound to a predictor's latest output (valid after run())."""

    def __init__(self, predictor: "Predictor"):
        self._p = predictor

    def copy_to_cpu(self):
        if self._p._last_out is None:
            raise RuntimeError("no output yet: call Predictor.run() first")
        return self._p._last_out.numpy()

    def shape(self):
        if self._p._last_out is None:
            raise RuntimeError("no output yet: call Predictor.run() first")
        return self._p._last_out.shape


class _Handle:
    """Zero-copy tensor handle parity (reference ZeroCopyTensor)."""

    def __init__(self, t: Tensor):
        self._t = t

    def reshape(self, shape):
        self._t._assign_array(jnp.zeros(shape, self._t._data.dtype))

    def copy_from_cpu(self, arr):
        self._t._assign_array(jnp.asarray(np.asarray(arr)))

    def share_external_data(self, arr):
        """Adopt an existing device array WITHOUT a host round trip
        (reference share_external_data zero-copy path)."""
        if isinstance(arr, Tensor):
            self._t._assign_array(arr._data)
        elif isinstance(arr, jax.Array):
            self._t._assign_array(arr)
        else:
            self._t._assign_array(jnp.asarray(arr))

    def copy_to_cpu(self):
        return self._t.numpy()

    def shape(self):
        return self._t.shape


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)


# ---------------------------------------------------------------------
# Int8-native serving (consumes the _int8_payload the PTQ pass records)
# ---------------------------------------------------------------------

class Int8Linear(paddle.nn.Layer):
    """Weight-only-int8 serving Linear: HBM holds the int8 payload +
    per-output-channel scales; dequantization happens INSIDE the
    compiled program at the matmul edge, where XLA fuses it into the
    GEMM read (the int8->bf16 convert rides the HBM->MXU path). This is
    the deployable form of the PTQ weight-only pass — reference:
    the int8 weight-only path of analysis_predictor's quant passes."""

    def __init__(self, weight_q, weight_scale, bias=None,
                 compute_dtype="float32"):
        super().__init__()
        from paddle_tpu.core import dtype as dtype_mod
        self._compute_dtype = dtype_mod.convert_dtype(compute_dtype)
        wq = weight_q if isinstance(weight_q, Tensor) else \
            Tensor(np.asarray(weight_q, np.int8))
        sc = weight_scale if isinstance(weight_scale, Tensor) else \
            Tensor(np.asarray(weight_scale, np.float32))
        self.register_buffer("weight_q", wq)
        self.register_buffer("weight_scale", sc)
        self.bias = None
        if bias is not None:
            self.bias = bias if isinstance(bias, Tensor) else Tensor(bias)

    def forward(self, x):
        from paddle_tpu.core.dispatch import run_op

        def f(a, wq, sc, *rest):
            w = wq.astype(self._compute_dtype) * sc.reshape(1, -1)
            out = a.astype(self._compute_dtype) @ w
            if rest:
                out = out + rest[0]
            return out
        args = [x, self.weight_q, self.weight_scale]
        if self.bias is not None:
            args.append(self.bias)
        return run_op("int8_linear", f, *args, differentiable=False)


def apply_int8_rewrite(layer, compute_dtype="float32"):
    """Swap every Linear carrying an _int8_payload for an Int8Linear
    holding the int8 buffer natively. Returns the count swapped."""
    from paddle_tpu.nn.layer.common import Linear as _Linear
    n = 0
    for name, sub in list(layer._sub_layers.items()):
        if isinstance(sub, _Linear) and \
                getattr(sub.weight, "_int8_payload", None) is not None:
            q, scale = sub.weight._int8_payload
            layer._sub_layers[name] = Int8Linear(
                Tensor(np.asarray(q, np.int8)),
                Tensor(np.asarray(scale, np.float32).reshape(-1)),
                bias=sub.bias, compute_dtype=compute_dtype)
            n += 1
        else:
            n += apply_int8_rewrite(sub, compute_dtype)
    return n


def save_int8_model(predictor: Predictor, path: str):
    """Write the int8-native serving artifact: one npz holding each
    quantized Linear's (int8 payload, scales) plus every other state
    tensor in fp. Load with `load_int8_model(layer, path)`."""
    layer = predictor._layer
    if not predictor._config._int8_weights:
        raise ValueError("enable_int8_weight_only() first: the int8 "
                         "payload is recorded by that pass")
    entries = {}
    for name, p in layer.named_parameters():
        payload = getattr(p, "_int8_payload", None)
        if payload is not None:
            q, scale = payload
            entries[name + ".int8"] = np.asarray(q, np.int8)
            entries[name + ".scale"] = np.asarray(scale,
                                                  np.float32).reshape(-1)
        else:
            entries[name] = np.asarray(p._data)
    for name, b in layer.named_buffers():
        entries["buffer:" + name] = np.asarray(b._data)
    np.savez(path, **entries)


def load_int8_model(layer, path: str, compute_dtype="float32"):
    """Restore an int8 serving artifact into a freshly-built layer:
    quantized Linears are swapped to Int8Linear (int8 stays int8 in
    HBM), everything else is loaded as saved."""
    data = np.load(path if path.endswith(".npz") else path + ".npz")
    int8_weights = {k[:-len(".int8")]: data[k] for k in data.files
                    if k.endswith(".int8")}
    scales = {k[:-len(".scale")]: data[k] for k in data.files
              if k.endswith(".scale")}
    for name, p in layer.named_parameters():
        if name in int8_weights:
            # restore QDQ numerics for every quantized param; Linear
            # weights are then swapped to int8-native storage below
            # (non-Linear quantized params, e.g. embeddings, serve the
            # dequantized values — same numerics, fp storage)
            q, sc = int8_weights[name], scales[name]
            ax = -1 if q.ndim == 2 else 0
            shape = [1] * q.ndim
            shape[ax % q.ndim] = -1
            deq = q.astype(np.float32) * sc.reshape(shape)
            p._assign_array(jnp.asarray(deq, p._data.dtype))
            p._int8_payload = (q, sc)
        elif name in data.files:
            p._assign_array(jnp.asarray(data[name]))
    for name, b in layer.named_buffers():
        key = "buffer:" + name
        if key in data.files:
            b._assign_array(jnp.asarray(data[key]))
    apply_int8_rewrite(layer, compute_dtype)
    return layer


def __getattr__(name):
    # serving sessions live in .decode; export them lazily so importing
    # paddle_tpu.inference stays light (the decode module pulls model
    # machinery). The robustness vocabulary (request states, admission
    # exceptions) lives in .admission — stdlib-light, but exported the
    # same way for one import surface.
    if name in ("DecodeSession", "ContinuousBatchingSession"):
        from . import decode
        return getattr(decode, name)
    if name in ("RequestState", "RequestResult", "AdmissionRejected",
                "ServingStepError", "AdmissionController"):
        from . import admission
        return getattr(admission, name)
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")
