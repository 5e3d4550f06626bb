"""One cell of BENCHMARK.json, once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: require a TPU with the cell's number of chips (else exit
non-zero with nothing on stdout), set up, warm every shape the window
uses, check correctness against the plain reference the configuration's
file names, measure for --seconds, print earlier lines freely and LAST one
JSON object:

    {"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
     "compared"}

`compared` holds each number the verdict rests on beside its limit; the
same go to standard error as its last lines.

With --trace 0 the metrics are the cell's end-to-end metrics; with
--trace 1 its per-layer metrics, read by benchmarks/layer_metrics/<name>.py
from a run that turns the engine's spans on (FLAGS_trace_dir) and profiles
a few seconds of the window. No fallback to the CPU, no cached payload.
The compile cache goes where JAX_COMPILATION_CACHE_DIR says, else
<checkout>/.jax_cache (paddle_tpu.core.compile_cache.setup).
"""
from __future__ import annotations

import time

T_PROC0 = time.monotonic()      # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, ROOT]

from harness import (cells, common, host_spans, peaks,  # noqa: E402
                     trace_reduce)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    res = cells.resolve(args.workload, ROOT)
    trace = bool(args.trace)
    # inside the checkout, git-ignored; the engine's spans are on only
    # where FLAGS_trace_dir is set before paddle_tpu is imported
    trace_dir = os.path.join(ROOT, ".bench_tmp", "trace", args.workload)
    if trace:
        os.makedirs(trace_dir, exist_ok=True)
        os.environ["FLAGS_trace_dir"] = trace_dir

    devices = common.require_chips(int(res["cell"]["chips"]))
    device_peaks = peaks.device_peaks(devices[0].device_kind)
    kind = res["traffic"]["kind"]
    if kind == "train":
        from harness import train_driver as driver
    elif kind == "serve":
        from harness import serve_driver as driver
    else:
        raise SystemExit(f"traffic kind {kind!r}: no such driver")
    out = driver.run(res, args.seed, args.seconds, trace,
                     T_PROC0, devices, trace_dir)

    device = out["device"]
    if not trace:
        metrics = dict(out["end_to_end"], setup_s=out["setup_s"])
        units = {m["name"]: m["unit"] for m in res["end_to_end"]}
        missing = sorted(set(units) - {k for k, v in metrics.items()
                                       if v is not None})
        if missing:
            raise SystemExit(f"run.py: the window gave no {missing}")
        line = {"metrics": {k: {"value": float(metrics[k]), "unit": units[k]}
                            for k in units}}
    else:
        run = dict(out["run"], cell=res["cell"], config=res["config"],
                   traffic=res["traffic"], peaks=device_peaks,
                   device=device, window_s=out["window_s"],
                   setup_s=out["setup_s"])
        line = {"metrics": cells.read_layer_metrics(res, run)}
        reduced = run["trace"]
        if reduced is None:
            raise SystemExit("run.py: the trace holds no device operation")
        device.update(busy_s=trace_reduce.mean_busy_s(reduced),
                      window_s=reduced["window_s"])
        line["breakdown"] = trace_reduce.breakdown(
            reduced, host_spans.longest_gaps_by_span(run))
        with open(os.path.join(trace_dir, "reduced.json"), "w") as fh:
            json.dump(reduced, fh, indent=1)
    compared = {k: {"value": float(v), "limit": float(limit)}
                for k, (v, limit) in out["compared"].items()}
    print(json.dumps({"correct": bool(out["correct"]),
                      "attempted": int(out["attempted"]),
                      "failed": int(out["failed"]), **line,
                      "device": device, "compared": compared}), flush=True)
    for k, c in compared.items():
        print(f"compared {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
