#!/usr/bin/env python
"""Launch a disaggregated serving cluster: router + N role workers.

    python scripts/serve_cluster.py --config cluster.toml
    python scripts/serve_cluster.py --workers 2 --role unified \
        --model-kind tiny_llama --max-batch 4 --max-len 64 --page-size 8

The config file (TOML on python >= 3.11, JSON anywhere) follows the shape
documented in docs/SERVING.md "Disaggregated deployment"; the flags build
the same dict for quick experiments. The router runs in THIS process
(ctrl-C tears the tier down); workers are real subprocesses that join
through the TCPStore lease/heartbeat loop.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def build_config(args) -> dict:
    if args.config:
        from paddle_tpu.serving_cluster import load_config

        return load_config(args.config)
    workers = []
    if args.prefill or args.decode:
        if args.prefill:
            workers.append({"role": "prefill", "count": args.prefill})
        if args.decode:
            workers.append({"role": "decode", "count": args.decode})
    else:
        workers.append({"role": args.role, "count": args.workers})
    return {
        "cluster": {"host": args.host, "port": args.port,
                    "ttl": args.ttl, "max_retries": args.max_retries,
                    "platform": args.platform},
        "model": {"kind": args.model_kind, "seed": args.seed},
        "engine": {"max_batch": args.max_batch, "max_len": args.max_len,
                   "page_size": args.page_size},
        "workers": workers,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", help="TOML/JSON cluster config file")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000,
                    help="router port (0 = ephemeral)")
    ap.add_argument("--workers", type=int, default=1,
                    help="unified worker count, at most one per chip "
                         "(ignored with --config)")
    ap.add_argument("--role", default="unified",
                    choices=("unified", "decode"))
    ap.add_argument("--prefill", type=int, default=0,
                    help="prefill-role worker count (disaggregated mode)")
    ap.add_argument("--decode", type=int, default=0,
                    help="decode-role worker count (disaggregated mode)")
    ap.add_argument("--model-kind", default="tiny_llama")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--ttl", type=float, default=5.0)
    ap.add_argument("--max-retries", type=int, default=2)
    ap.add_argument("--platform", default=None,
                    help="jax platform override for workers (e.g. cpu)")
    args = ap.parse_args(argv)

    from paddle_tpu.serving_cluster import launch_cluster

    cfg = build_config(args)
    print("launching cluster:", json.dumps(cfg, indent=1))
    cluster = launch_cluster(cfg)
    host, port = cluster.address
    print(f"router serving on http://{host}:{port} "
          f"({cluster.pool.alive_count()} workers); ctrl-C to stop")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        print("shutting down...")
    finally:
        cluster.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
