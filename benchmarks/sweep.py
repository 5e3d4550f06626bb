"""Find an open-loop cell's knee: the highest rate the system sustains.

    python benchmarks/sweep.py --workload gpt3-medium.serve-longprompt \
        --rates 1,2,3,4,5,6,8 --seconds 20 --seed 1

One process, one engine; each rate is offered for --seconds by the same
load generator the cell uses (its file's rate overridden), and the engine
runs empty before the next. One JSON line per rate: requests offered and
completed inside the rate's window per second, the share of the requests
due in its first three quarters that were whole by its end, the queue's
depth in the middle and at the end, and the client's TTFT and inter-token
gap. A rate is sustained where those early requests all finished and the
queue is no deeper at the end than in the middle. Run once, when a cell is defined;
the cell's rate (0.8 of the knee) is written into its traffic file.
"""
from __future__ import annotations

import time

T_PROC0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, ROOT]

from harness import (cells, common, end_to_end, serve_driver,  # noqa: E402
                     stats)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    res = cells.resolve(args.workload, ROOT)
    common.require_chips(int(res["cell"]["chips"]))
    vocab = int(res["config"]["architecture"]["vocab_size"])
    engine, srv, url, _ = serve_driver.build(res, args.seed, T_PROC0)
    os.makedirs(os.path.join(ROOT, ".bench_tmp"), exist_ok=True)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            gen = serve_driver.LoadGen(
                url, res, args.seed, vocab, 0.0, args.seconds,
                os.path.join(ROOT, ".bench_tmp", "sweep.jsonl"), rate=rate)
            t0 = time.monotonic() + 0.2
            gen.release(t0)
            t1 = t0 + args.seconds
            serve_driver.sleep_until(t0 + args.seconds / 2)
            mid = engine.metrics.snapshot()
            serve_driver.sleep_until(t1)
            end = engine.metrics.snapshot()
            samples, _ = gen.finish(timeout=120)
            # let the engine run empty before the next rate
            while True:
                snap = engine.metrics.snapshot()
                if not snap["queue_depth"] and \
                        not snap["kv_pool"]["slots_used"]:
                    break
                time.sleep(0.2)
            def whole(r):      # its last token came inside the window
                return len(r["tokens"]) == r["asked"] \
                    and r["tokens"][-1] <= t1

            offered = sum(1 for r in samples if r["due"] <= t1)
            completed = sum(1 for r in samples if whole(r))
            # at any rate some requests are still in flight when the
            # window closes; those due in its first three quarters have
            # had a quarter of it to finish, unless a backlog grows
            early = [r for r in samples
                     if r["due"] <= t0 + 0.75 * args.seconds]
            ttft = end_to_end.ttft_ms(samples, t0, t1)
            common.log(
                rate=rate, offered_per_s=offered / args.seconds,
                completed_per_s=completed / args.seconds,
                early_requests=len(early),
                early_completed_share=sum(map(whole, early))
                / max(len(early), 1),
                queue_mid=mid["queue_depth"], queue_end=end["queue_depth"],
                slots_used_end=end["kv_pool"]["slots_used"],
                ttft_ms_p50=stats.percentile(ttft, 50),
                ttft_ms_p90=stats.percentile(ttft, 90),
                itl_ms_p95=end_to_end.itl_ms_p95(samples, t0, t1),
                errors=sum(1 for r in samples if r["error"]
                           and not r["cut"]))
    finally:
        srv.stop(drain=False)
    return 0


if __name__ == "__main__":
    sys.exit(main())
