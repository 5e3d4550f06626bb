"""Inference worker behind the C deployment ABI (cpp/pd_infer.cc).

Role of the reference's C API runtime
(paddle/fluid/inference/capi_exp/pd_inference_api.h + pd_predictor.cc):
let a NON-PYTHON service serve a saved `.pdmodel`. On this stack the
program format is serialized StableHLO and the executor is the JAX/XLA
runtime, which lives in-process here; the C shim spawns this worker and
speaks a length-prefixed binary protocol over stdin/stdout:

  worker -> client on startup:
      magic  b"PDIS"  u32 version
      u32 n_inputs   then per input:  dtype-str blob, u32 ndim,
                                      i64 dims[ndim] (-1 = dynamic)
      u32 n_outputs  (output shapes depend on inputs; sizes travel
                      per-run)
  client -> worker per request:
      b"RUN_"  then per input: u64 nbytes + raw bytes (C-order,
      dtype/shape per the announced spec; a single dynamic dim is
      resolved by size — TWO dynamic dims in ONE input are ambiguous
      from a byte count and fail that request with a clear ERR_)
  worker -> client per response:
      b"OUT_"  u32 n_outputs  then per output: dtype-str blob, u32 ndim,
      i64 dims[ndim], u64 nbytes + raw bytes
      on failure: b"ERR_"  u64 len + utf-8 message
  client -> worker: b"BYE_" ends the session.

Run: python -m paddle_tpu.inference.serve <model_prefix>

Multi-request serving (`--engine`): route every RUN_ through the
dynamic-batching ServingEngine (warm per-bucket executables, metrics),
or serve HTTP instead of the pipe with `--http PORT`
(inference/serving/server.py endpoints: /predict, /healthz, /metrics).
"""
from __future__ import annotations

import argparse
import io
import struct
import sys

import numpy as np

MAGIC = b"PDIS"
VERSION = 1


def _w(fh, data: bytes):
    fh.write(data)


def _blob(fh, b: bytes):
    _w(fh, struct.pack("<Q", len(b)) + b)


def _read_exact(fh, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = fh.read(n - len(buf))
        if not chunk:
            raise EOFError("client closed the pipe")
        buf += chunk
    return buf


def decode_input(raw: bytes, spec: dict, index: int) -> np.ndarray:
    """Reconstruct one input array from raw bytes + its announced spec.
    A single dynamic (None) dim resolves from the byte count; more than
    one in the same input is ambiguous (a size factors many ways), so it
    raises a clear error instead of reshaping into garbage."""
    dt = np.dtype(spec["dtype"])
    arr = np.frombuffer(raw, dtype=dt)
    shape = list(spec["shape"])
    dyn = [d for d, v in enumerate(shape) if v is None]
    if len(dyn) > 1:
        raise ValueError(
            f"input {index}: spec {spec['shape']} has {len(dyn)} dynamic "
            f"dims; the pipe protocol ships only a byte count, which "
            f"cannot resolve more than one — export with at most one "
            f"dynamic axis per input, or serve over HTTP JSON "
            f"(--engine --http) where shapes travel explicitly")
    known = 1
    for v in shape:
        if v is not None:
            known *= int(v)
    if dyn:
        if known == 0 or arr.size % max(known, 1):
            raise ValueError(
                f"input {index}: {arr.size} elements do not divide into "
                f"spec {spec['shape']}")
        shape[dyn[0]] = arr.size // max(known, 1)
    return arr.reshape(shape)


def run_worker(prefix: str, runner=None, predictor=None) -> int:
    """Speak the pipe protocol; `runner(inputs)->outputs` defaults to the
    single-request Predictor, or the ServingEngine under --engine (which
    passes its already-loaded `predictor` so the model isn't
    deserialized — and resident — twice)."""
    # stdout is the PROTOCOL channel: anything the runtime prints must
    # not corrupt it
    proto_out = sys.stdout.buffer
    sys.stdout = sys.stderr

    from . import Config, Predictor

    pred = predictor if predictor is not None else Predictor(Config(prefix))
    specs = pred._meta["input_specs"]
    if runner is None:
        runner = pred.run

    _w(proto_out, MAGIC + struct.pack("<I", VERSION))
    _w(proto_out, struct.pack("<I", len(specs)))
    for s in specs:
        _blob(proto_out, s["dtype"].encode())
        dims = [(-1 if d is None else int(d)) for d in s["shape"]]
        _w(proto_out, struct.pack("<I", len(dims)))
        _w(proto_out, struct.pack(f"<{len(dims)}q", *dims))
    _w(proto_out, struct.pack("<I", len(pred._meta["output_names"])))
    proto_out.flush()

    fin = sys.stdin.buffer
    while True:
        try:
            op = _read_exact(fin, 4)
        except EOFError:
            return 0
        if op == b"BYE_":
            return 0
        if op != b"RUN_":
            _w(proto_out, b"ERR_")
            _blob(proto_out, f"bad opcode {op!r}".encode())
            proto_out.flush()
            return 1
        # read EVERY input's bytes before decoding any: a decode error
        # mid-request must not leave later blobs unread in the pipe
        # (stale bytes would be parsed as the next opcode — permanent
        # protocol desync on multi-input models)
        raws = []
        for _ in specs:
            (nbytes,) = struct.unpack("<Q", _read_exact(fin, 8))
            raws.append(_read_exact(fin, nbytes))
        try:
            inputs = [decode_input(raw, s, i)
                      for i, (s, raw) in enumerate(zip(specs, raws))]
            outs = runner(inputs)
            # serialize the ENTIRE reply before touching the pipe: an
            # exception mid-serialization must not leave a half-written
            # OUT_ on the wire, where the ERR_ fallback would land inside
            # the C client's output parse and desync the ABI for good
            # (the input side guards the same way by pre-reading blobs)
            reply = io.BytesIO()
            _w(reply, b"OUT_" + struct.pack("<I", len(outs)))
            for o in outs:
                o = np.ascontiguousarray(o)
                _blob(reply, str(o.dtype).encode())
                _w(reply, struct.pack("<I", o.ndim))
                _w(reply, struct.pack(f"<{o.ndim}q", *o.shape))
                _blob(reply, o.tobytes())
            _w(proto_out, reply.getvalue())
            proto_out.flush()
        except Exception as e:  # noqa: BLE001 — surface to the C client
            _w(proto_out, b"ERR_")
            _blob(proto_out, repr(e)[:4000].encode())
            proto_out.flush()


def generate_presets() -> dict:
    """name -> the module whose PRESETS holds it: every model `--generate`
    serves, each kind from its own module's table (models.gpt through the
    engine's own `GPTPasses`; models.lfm2 and models.brumby through the
    step their configuration supplies, `cfg.serving_passes()`). A module of
    another kind joins by the same rule: a `PRESETS` table and, beside it,
    `init_params(cfg)`."""
    from ..models import brumby, gpt, lfm2

    return {name: module for module in (gpt, lfm2, brumby)
            for name in module.PRESETS}


def build_generator(preset: str, state_dict: str | None = None,
                    draft: str | None = None, **engine_kw):
    """The GenerativeEngine `--generate PRESET` serves: a preset of
    models.gpt, models.lfm2 or models.brumby with seeded demo weights (or,
    for a GPT, `state_dict` loaded into it), optionally a `draft` preset
    for speculative decode; `engine_kw` goes to the engine. Returned warmed
    and started."""
    import paddle_tpu as paddle
    from ..models import gpt
    from .serving import GenerativeEngine
    from .serving.generate import stack_gpt_params

    def stacked(name, state=None):
        """(params, cfg) as the engine's programs take them. A GPT's are
        copied out of a model that dies here, before the engine warms up:
        its weights are not held a second time beside the pools. A model
        of another kind draws its own on the device, an array at a time,
        in the type it is served in: no model object, no float32 copy."""
        paddle.seed(0)
        module = generate_presets()[name]
        if module is not gpt:
            if state:
                raise ValueError(
                    f"{name}: no checkpoint format for {module.__name__} "
                    f"yet; its weights are seeded")
            return module.init_params(module.PRESETS[name]), \
                module.PRESETS[name]
        model = gpt.GPTForCausalLM(gpt.PRESETS[name])
        model.eval()
        if state:
            model.set_state_dict(paddle.load(state))
        return stack_gpt_params(model)

    return GenerativeEngine(
        params=stacked(preset, state_dict),
        draft_params=None if draft is None else stacked(draft),
        **engine_kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.inference.serve",
        description="serve a saved .pdmodel: pipe-protocol worker by "
                    "default, dynamic-batching engine with --engine, "
                    "HTTP front-end with --http PORT")
    ap.add_argument("prefix", nargs="?", default=None,
                    help="model path prefix (the .pdmodel stem); "
                         "optional with --generate")
    ap.add_argument("--engine", action="store_true",
                    help="route requests through the ServingEngine "
                         "(bucketed dynamic batching, warm replicas)")
    ap.add_argument("--http", type=int, metavar="PORT", default=None,
                    help="serve HTTP on PORT instead of the stdin/stdout "
                         "pipe (implies --engine)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--max-batch-size", type=int, default=None)
    ap.add_argument("--batch-timeout-ms", type=float, default=None)
    ap.add_argument("--replicas", type=int, default=None)
    ap.add_argument("--max-queue-depth", type=int, default=None)
    ap.add_argument("--generate", metavar="PRESET", default=None,
                    help="also serve streaming generation (/generate) "
                         "from a PRESET of models.gpt, models.lfm2 or "
                         "models.brumby (e.g. gpt3-tiny, lfm2-tiny, "
                         "brumby-tiny; "
                         "seeded demo weights, or --state-dict to load "
                         "trained ones); requires --http")
    ap.add_argument("--state-dict", default=None,
                    help="checkpoint to load into the --generate model "
                         "(paddle_tpu.load state_dict path)")
    ap.add_argument("--slots", type=int, default=None,
                    help="--generate decode-batch capacity per worker")
    ap.add_argument("--draft", metavar="PRESET", default=None,
                    help="speculative decode: a models.gpt draft preset "
                         "(e.g. tiny-draft) proposing tokens the "
                         "--generate model verifies in one batched step")
    ap.add_argument("--spec-tokens", type=int, default=4,
                    help="tokens per speculative burst (with --draft)")
    ap.add_argument("--prefix-cache", type=int, default=0, metavar="N",
                    help="prefix-cache slots per KV class: prompts "
                         "sharing a pow2-aligned prefix prefill only "
                         "their tail")
    ap.add_argument("--kv-dtype", choices=("f32", "int8"), default=None,
                    help="--generate KV-cache pool precision (default: "
                         "the model's own — f32 for a GPT, bfloat16 for "
                         "an LFM2, which takes no other; a Brumby has no "
                         "K/V pool): int8 "
                         "stores quantized rows with per-(row, layer) "
                         "absmax scales — half the pool bytes, double "
                         "the slots per byte (DESIGN.md Quantized serving)")
    ap.add_argument("--quantize-weights", action="store_true",
                    help="weight-only int8 for the --generate model "
                         "(and draft): absmax per layer at warmup, "
                         "dequant-in-matmul at serve time")
    ap.add_argument("--admin", action="store_true",
                    help="mount the /admin plane (fleet actuation, "
                         "drain, /admin/kv handoff import); keep the "
                         "port private")
    ap.add_argument("--fabric", metavar="STORE", default=None,
                    help="join the serving fabric: registry "
                         "endpoint(s) (host:port, comma-separated for "
                         "a quorum); implies --admin")
    ap.add_argument("--pool", default=None,
                    help="fabric role override, comma list — "
                         "'prefill' or 'decode' makes this host a "
                         "specialized disaggregated-serving pool "
                         "member (default: derived from the mounted "
                         "fronts)")
    args = ap.parse_args(argv)

    if args.generate is None and args.prefix is None:
        ap.error("need a model prefix (or --generate PRESET)")
    if args.generate is not None and args.http is None:
        ap.error("--generate needs --http PORT (streaming rides HTTP)")

    if not args.engine and args.http is None:
        return run_worker(args.prefix)

    from .serving import ServingEngine, ServingHTTPServer

    generator = None
    if args.generate is not None:
        presets = generate_presets()
        for what, name in (("preset", args.generate),
                           ("draft preset", args.draft)):
            if name is not None and name not in presets:
                ap.error(f"unknown {what} {name!r}; have "
                         f"{sorted(presets)}")
        generator = build_generator(
            args.generate, state_dict=args.state_dict, draft=args.draft,
            slots=args.slots,
            replicas=args.replicas if args.replicas else 1,
            max_queue_depth=args.max_queue_depth,
            spec_tokens=args.spec_tokens,
            prefix_cache_slots=args.prefix_cache,
            kv_dtype=args.kv_dtype,
            quantize_weights=args.quantize_weights)

    engine = None
    if args.prefix is not None:
        engine = ServingEngine(
            args.prefix, max_batch_size=args.max_batch_size,
            batch_timeout_ms=args.batch_timeout_ms, replicas=args.replicas,
            max_queue_depth=args.max_queue_depth)
    if args.http is not None:
        admin = bool(args.admin or args.fabric)
        srv = ServingHTTPServer(engine, host=args.host, port=args.http,
                                generator=generator, admin=admin)
        agent = None
        if args.fabric:
            from ..distributed.store import make_store
            from .fabric import HostAgent

            pools = None
            if args.pool:
                pools = [p.strip() for p in args.pool.split(",")
                         if p.strip()]
            agent = HostAgent(srv, make_store(args.fabric),
                              pools=pools).start()
        what = []
        if engine is not None:
            what.append(f"predict[{args.prefix}]")
        if generator is not None:
            what.append(f"generate[{args.generate}]")
        if agent is not None:
            what.append(f"fabric[{','.join(agent.lease.pools)}]")
        print(f"serving {' + '.join(what)} on "
              f"http://{srv.host}:{srv.port}", file=sys.stderr)
        try:
            srv.serve_forever()
        finally:
            if agent is not None:
                agent.stop()
        return 0
    try:
        return run_worker(args.prefix, runner=engine.predict,
                          predictor=engine._predictor)
    finally:
        engine.shutdown(drain=True)


if __name__ == "__main__":
    sys.exit(main())
