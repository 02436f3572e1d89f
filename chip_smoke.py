"""On-chip bring-up check of the LPQ-ANN serve path on a TPU.

    python chip_smoke.py             one chip: the index arms of ARMS
                                     served at n = 2,000,000, d = 256 (ip)
    python chip_smoke.py --chips 4   four chips: sharded and replicated
                                     serving at n = 4,000,000, d = 256

Each arm goes through the user's entry point, ``repro.launch.serve.main``,
under the ``tpu-serve`` runtime profile, which refuses to start unless the
first device is a TPU.  The data is the ``product60m`` shape of
``configs/lpq_ann.py`` (narrow-band product embeddings, inner product),
cut to about 1/30 of its rows for one chip.  Every arm serves mixed
requests over the buckets 1/8/32/256.  Then each arm is checked:

  * its compiled 256-query bucket holds the arm's scan kernel as a Mosaic
    ``tpu_custom_call`` (the ivf arm gathers instead and is exempt);
  * the fused scan stage equals the same store scanned with
    ``use_pallas=False`` (XLA) bit for bit: scores, and ids in order
    (every scan orders equal scores by id);
  * the planned (bucketed) answer equals the fused stage it is built on;
  * recall@10 against a blocked fp32 exact top-k at ``precision=HIGHEST``
    meets the arm's floor, where it has one (otherwise it is printed).

With ``--chips 4`` only the paths that span chips run: ``flat,lpq8`` and
``ivf64,lpq8`` served with ``--shards 4`` must equal their unsharded plans
on chip 0 bit for bit, and ``--replicas 4`` must put each replica's index
on its own chip.

Lines starting with ``[smoke]`` are bring-up facts (build and compile
seconds, recall, memory), not benchmark numbers.  Every arm runs even
after another failed, so one run reports every fault; any failure exits
non-zero without printing the last line, one JSON object naming the
device.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import traceback

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N_ONE, N_FOUR, D = 2_000_000, 4_000_000, 256
CHECK_Q = 256               # queries checked per arm (the largest bucket)
MIXED = ["--mixed", "--batch", "256", "--requests", "8"]   # 1/8/32/256

#: (factory, serve args, scan kernel or None, recall@10 floor or None, ks)
ARMS = (
    ("flat,lpq8@gaussian:3", MIXED, "fused_topk_pallas", 0.90, (10, 100)),
    # the floored int4 arm reranks 400 candidates at both k: the default
    # depth (4k) at k=100.  At k=10 the default depth is 40; that arm runs
    # without a floor so that the recall users get by default is printed.
    ("flat,lpq4+r32", MIXED + ["--rerank-depth", "400"],
     "fused_topk4_pallas", 0.95, (10, 100)),
    ("flat,lpq4+r32", MIXED, "fused_topk4_pallas", None, (10,)),
    ("pq32x4+lpq", MIXED, "fused_adc4_pallas", None, (10, 100)),
    ("ivf256,lpq8", MIXED, None, None, (10, 100)),
)


#: failed checks of this run, reported together at the end
FAILURES: list[str] = []


def _fail(msg: str) -> None:
    print(f"[smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    FAILURES.append(msg)


def _exact_ip(q, x):
    return jnp.dot(q, x.T, precision=jax.lax.Precision.HIGHEST)


def _recall_at_10(corpus, queries, ids) -> float:
    from repro.engine import chunked_topk

    _s, gt = chunked_topk(queries, corpus, 10, _exact_ip, chunk=65536)
    gt, got = np.asarray(gt), np.asarray(ids)[:, :10]
    return float(np.mean([len(set(g) & set(r)) / 10 for g, r in zip(gt, got)]))


def _same(a, b) -> bool:
    """Bit-equal answers: scores, and ids in the same order.  Every scan
    orders equal scores by id, so ties are no excuse for a difference."""
    return (np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
            and np.array_equal(np.asarray(a[1]), np.asarray(b[1])))


def _device_bytes(key: str) -> list[int]:
    return [int((d.memory_stats() or {}).get(key, 0)) for d in jax.devices()]


def _serve(factory: str, n: int, k: int, extra: list[str]) -> dict:
    from repro.launch import serve

    return serve.main(["--profile", "tpu-serve", "--index", factory,
                       "--n", str(n), "--d", str(D), "--k", str(k)] + extra)


def _has_kernel(hlo_text: str, kernel: str) -> bool:
    """Does the compiled program run ``kernel`` as a Mosaic custom call?"""
    return any("tpu_custom_call" in line and f"jit({kernel})/pallas_call"
               in line for line in hlo_text.splitlines())


def _check_arm(factory: str, extra: list[str], kernel, floor, k: int,
               kind: str):
    from repro import engine

    res = _serve(factory, N_ONE, k, extra)
    index, searcher = res["index"], res["searcher"]
    queries = res["queries"][:CHECK_Q]
    mosaic = kernel is not None and _has_kernel(
        searcher.lower(CHECK_Q).compile().as_text(), kernel)
    if kernel is not None and not mosaic:
        _fail(f"{factory} k={k}: no {kernel} in the compiled bucket")

    planned = searcher(queries)
    if kernel is not None:
        store, metric = index.store, index.metric
        depth = searcher.rerank.depth if searcher.rerank else k

        def stage(use_pallas):       # the store is an argument, not a constant
            return jax.jit(lambda q, st: engine.topk(
                q, st, depth, metric, use_pallas=use_pallas)[:2])(queries, store)

        fused = stage(True)
        if not _same(fused, stage(False)):
            _fail(f"{factory} k={k}: fused scan != use_pallas=False scan")
        if searcher.rerank is not None:
            fused = engine.rerank_among(queries, searcher.rerank.store,
                                        fused[1], k, metric)[:2]
        if not _same((planned.scores, planned.ids), fused):
            _fail(f"{factory} k={k}: planned answer != its fused stage")
    recall = _recall_at_10(res["corpus"], queries, planned.ids)
    print(f"[smoke] arm={factory} k={k} rerank_depth="
          f"{searcher.rerank.depth if searcher.rerank else 0} device_kind={kind} "
          f"build_s={res['build_s']} first_calls_s={res['warm_s']} "
          f"recall@10={recall} memory_bytes={index.memory_bytes()} "
          f"peak_bytes_in_use={_device_bytes('peak_bytes_in_use')[0]} "
          f"scan_kernel={kernel or 'none (gather path)'} in_bucket={mosaic}",
          flush=True)
    if floor is not None and recall < floor:
        _fail(f"{factory} k={k}: recall@10 {recall} < floor {floor}")


def _check_sharded(factory: str, extra: list[str], kind: str) -> None:
    res = _serve(factory, N_FOUR, 10, extra + ["--shards", "4"])
    index, sharded = res["index"], res["searcher"]
    queries = res["queries"][:CHECK_Q]
    a = sharded(queries)
    b = index.searcher(10, sharded.params, batch_sizes=sharded.batch_sizes)(
        queries)
    same = _same((a.scores, a.ids), (b.scores, b.ids))
    print(f"[smoke] arm={factory} shards=4 device_kind={kind} "
          f"placement={a.stats.get('placement')} sharded_equals_unsharded="
          f"{same} bytes_in_use_per_device={_device_bytes('bytes_in_use')} "
          f"memory_bytes={index.memory_bytes()}", flush=True)
    if not same:
        _fail(f"{factory}: sharded answers differ from the unsharded plan")


def _check_replicas(kind: str) -> None:
    res = _serve("flat,lpq8@gaussian:3", N_FOUR, 10, MIXED + ["--replicas", "4"])
    reps = res["replica_searchers"]
    homes = [{d.id for d in s.index.store.data.devices()} for s in reps]
    want = [{d.id} for d in jax.devices()[:4]]
    queries = res["queries"][:CHECK_Q]
    answers = [np.asarray(s(queries).ids) for s in reps]
    agree = all(np.array_equal(answers[0], x) for x in answers[1:])
    print(f"[smoke] replicas=4 device_kind={kind} index_devices={homes} "
          f"replicas_agree={agree} "
          f"bytes_in_use_per_device={_device_bytes('bytes_in_use')}",
          flush=True)
    if homes != want:
        _fail(f"replica indexes live on {homes}, expected {want}")
    if not agree:
        _fail("replicas answer the same queries differently")


def _run(check, *args) -> None:
    """One check; a crash is recorded as a failure and the next runs."""
    try:
        check(*args)
    except Exception:  # noqa: BLE001 — report every arm's fault in one run
        _fail(f"{check.__name__}{args[:-1]} raised:\n"
              + traceback.format_exc())
    gc.collect()
    jax.clear_caches()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded and replicated paths")
    args = ap.parse_args(argv)

    from repro.runtime import profile as rtprofile

    try:
        rtprofile.apply(rtprofile.resolve("tpu-serve"))
    except RuntimeError as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr)
        raise SystemExit(1)
    devs = jax.devices()
    kind = devs[0].device_kind
    if len(devs) < args.chips:
        print(f"[smoke] FAIL: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devs)}", file=sys.stderr)
        raise SystemExit(1)
    if args.chips == 1:
        for factory, extra, kernel, floor, ks in ARMS:
            for k in ks:
                _run(_check_arm, factory, extra, kernel, floor, k, kind)
    else:
        _run(_check_sharded, "flat,lpq8@gaussian:3", MIXED, kind)
        _run(_check_sharded, "ivf64,lpq8", MIXED, kind)
        _run(_check_replicas, kind)
    if FAILURES:
        print(f"[smoke] {len(FAILURES)} check(s) failed", file=sys.stderr)
        raise SystemExit(1)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind, "count": len(devs)}}))


if __name__ == "__main__":
    main()
