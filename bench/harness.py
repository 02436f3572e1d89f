"""One run of one cell: set up, drive the window, check, report.

``run_cell`` is everything ``bench/run.py`` does after reading its
arguments.  Set-up draws the data on the device from the seed, builds the
index through ``repro.knn.make_index``, plans it with ``index.searcher``
and calls it once with every request size the cell's traffic sends, so
that every program is compiled (or read from the compile cache) before
the window opens; it then takes the memory the chip holds to serve.
After the window it reads the build's peak memory, reads an ivf index's
coarse quantizer back to the host, frees the program's state, and runs
the reference over a sample of the window's answers drawn from the
seed, the longest request among them.

A mix whose ``layout`` is ``replicas``, or that asks for C clients
(``bench/traffic.py``), is served by ``Router``: the index, built once on
the first chip, is copied to each of the cell's chips, each chip plans
and warms its own Searcher, and the program's ``ReplicaSet`` routes the
requests of C closed-loop client threads (``loop.closed_pool``) to the
least-loaded replica.  At the ``single`` layout and one client the window
calls the Searcher directly from the calling thread (``loop.closed``).

``system`` lets a test, a planted fault or the control put something
else in the Searcher's place: ``system(searcher, setup)`` returns what
the window calls instead, once for each replica.  ``setup`` holds the
data's generator, keys and constants, the cell, the index (the replica's
copy), ``make_searcher``, the query pool, and the replica's number, the
number of replicas and the replica's device.  The benchmark's own runs
never pass it.
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace

import numpy as np

from bench import data, loop, reference, registry, trace, traffic

#: jax.monitoring events that mean a program was compiled or loaded
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)


@dataclasses.dataclass
class RunView:
    """What a per-layer metric's ``read(run)`` sees."""

    records: list
    n: int
    d: int
    row_bytes: int
    device_kind: str
    trace: dict | None = None        # trace.record(...) of the window
    reduced: dict | None = None      # trace.reduce(...) of it
    replicas: int = 1
    # the request rows the program's router logged in the window (its
    # Telemetry's ``request`` events), or None without a router
    replica_log: list | None = None


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def setup_jax(chips: int, require_tpu: bool):
    """The devices the cell runs on.  With ``require_tpu`` the program's
    ``tpu-serve`` profile is applied: it refuses any first device that is
    not a TPU and keeps the compile cache inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says)."""
    import jax

    if require_tpu:
        from repro.runtime import profile as rt

        try:
            rt.apply(rt.resolve("tpu-serve"))
        except RuntimeError as e:
            raise NoChip(str(e)) from None
        # cache every program, however fast it compiled, so a second run
        # of the cell compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


class _CompileCounter:
    def __init__(self):
        import jax

        self.count = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, _secs, **_kw):
        if self.on and event in COMPILE_EVENTS:
            self.count += 1


def _device_memory(devs, key: str) -> int:
    return max(int((d.memory_stats() or {}).get(key, 0)) for d in devs)


def _sample(records: list, rng: np.random.Generator, want: int) -> list:
    """Whole answered requests to compare: the longest that each replica
    served first, then others in an order drawn from the seed, until they
    hold ``want`` queries."""
    done = [i for i, r in enumerate(records) if r["done"] is not None]
    if not done:
        return []
    served = {}
    for i in done:
        served.setdefault(records[i]["stats"].get("replica", 0), []).append(i)
    longest = [max(ix, key=lambda i: (records[i]["size"], -i))
               for _r, ix in sorted(served.items())]
    order = longest + [i for i in rng.permutation(done) if i not in longest]
    out, have = [], 0
    for n, i in enumerate(order):
        out.append(records[i])
        have += records[i]["size"]
        if have >= want and n >= len(longest) - 1:
            break
    return out


class Router:
    """One replica of the index on each of ``devs`` behind the program's
    router, ``repro.dist.replica.ReplicaSet``, placed as ``launch/serve.py``
    places its replicas: called with a request, it routes it to the
    least-loaded replica and returns the answer once it is ready on that
    replica's chip, with the replica's number in its stats; the caller
    copies it to the host.

    ``index`` was built on the first device; each other replica gets its
    copy with ``jax.device_put(index, device)``, one at a time, plans its own
    Searcher on it and calls it with every size in ``warm``, so no worker
    compiles in the window.  As in serve, requests go in as host arrays:
    the Searcher uploads and pads them where JAX puts them by default.
    The router logs one request row per request served to a
    ``Telemetry`` of its own.
    """

    def __init__(self, devs, index, make_searcher, setup: dict, system,
                 warm: list):
        import jax

        from repro.dist.replica import ReplicaSet
        from repro.runtime.telemetry import Telemetry

        self.searchers = []
        self.telemetry = Telemetry()

        def make_replica(r):
            # serve puts a copy on the first chip too, beside the built
            # index it keeps; the built index is the first replica here
            idx = index if r == 0 else jax.device_put(index, devs[r])
            searcher = make_searcher(idx)
            serve = searcher if system is None else system(searcher, {
                **setup, "index": idx, "replica": r, "device": devs[r]})
            for size in warm:
                jax.block_until_ready(serve(setup["pool"].queries[:size]).ids)
            self.searchers.append(searcher)

            def run(queries):
                res = serve(queries)
                jax.block_until_ready(res.ids)
                return r, res

            return run

        self.replicas = ReplicaSet(make_replica, len(devs),
                                   telemetry=self.telemetry)

    def __call__(self, queries):
        r, res = self.replicas.submit(
            queries, queries=int(queries.shape[0])).result()
        return SimpleNamespace(scores=res.scores, ids=res.ids,
                               stats={**res.stats, "replica": r})

    def close(self) -> list:
        """Stops the replicas once every request has been served; returns
        the request rows the router logged."""
        self.replicas.close()
        return [e for e in self.telemetry.events if e["type"] == "request"]


def build(cell, keys, devs):
    """The index and the ``make_searcher(index, **search)`` of the cell's
    plan (``search`` overrides the configuration's search parameters)."""
    import jax

    from repro.knn import SearchParams, make_index

    cfg, mix = cell.config, cell.mix
    gen = data.generator(cfg)
    consts = gen.consts(keys["data"])
    corpus = data.corpus(gen, keys["corpus"], int(cfg["n"]), consts)
    index = make_index(cfg["factory"], corpus, metric=cfg["metric"],
                       key=keys["build"])
    jax.block_until_ready(index)
    del corpus

    def make_searcher(idx, **search):
        params = SearchParams(**{**cfg.get("search", {}), **search})
        return idx.searcher(int(mix["k"]), params,
                            batch_sizes=tuple(cfg["batch_sizes"]))

    return gen, consts, index, make_searcher


def run_cell(cell, seed: int, seconds: float, with_trace: bool, *,
             t_start: float, require_tpu: bool = True, system=None,
             log=sys.stderr) -> dict:
    """One run; returns the result line's object."""
    devs = setup_jax(cell.chips, require_tpu)
    import jax

    cfg, mix = cell.config, cell.mix
    n, d = int(cfg["n"]), int(cfg["d"])
    layout, clients = mix.get("layout", "single"), int(mix.get("clients", 1))
    if layout not in ("single", "replicas"):
        raise ValueError(f"{cell.name}: unknown layout {layout!r}; "
                         "have 'single' and 'replicas'")
    # one replica on each of the cell's chips, or one Searcher on the first
    replicas = len(devs) if layout == "replicas" else 1
    keys = dict(zip(("data", "corpus", "queries", "build"),
                    jax.random.split(data.key_from_seed(seed), 4)))
    if "seed" in cfg["data"]:
        # a data set fixed by the configuration, as a published one is:
        # every run seed serves the same rows, index and queries, and
        # draws its own order of request sizes and its checked sample
        keys = dict(zip(keys, jax.random.split(
            data.key_from_seed(int(cfg["data"]["seed"])), 4)))
    rng_traffic = np.random.default_rng([seed, 1])
    rng_sample = np.random.default_rng([seed, 2])

    def phase(name, t=[t_start]):
        now = time.perf_counter()
        print(f"[bench] {name}: {now - t[0]:.3f} s", file=log, flush=True)
        t[0] = now

    phase("start")
    gen, consts, index, make_searcher = build(cell, keys, devs)
    phase("data and build")
    row_bytes = int(index.store.row_bytes)
    pool = traffic.QueryPool(np.asarray(data.queries(
        gen, keys["queries"], int(mix["pool"]), consts)))
    warm = traffic.warm_sizes(mix)
    # an ivf index's coarse quantizer, which the reference reads back
    coarse = ((index.centroids, index.lists) if hasattr(index, "lists")
              else None)
    setup = {"gen": gen, "consts": consts, "keys": keys, "cell": cell,
             "index": index, "make_searcher": make_searcher,
             "coarse": coarse, "pool": pool, "replica": 0,
             "replicas": replicas, "device": devs[0]}
    router = None
    if layout == "single" and clients == 1:
        searcher = make_searcher(index)
        serve = searcher if system is None else system(searcher, setup)
        for size in warm:
            jax.block_until_ready(serve(pool.queries[:size]).ids)
    else:
        serve = router = Router(devs[:replicas], index, make_searcher, setup,
                                system, warm)
        searcher = router.searchers[0]
    del index
    gc.collect()
    phase("plan and warm-up")

    # what the chip holds while it serves: the bytes in use once warm,
    # plus the largest temporary space one of the cell's buckets needs
    held = _device_memory(devs, "bytes_in_use")
    print("[bench] bytes in use on each chip: "
          + ", ".join(str((d.memory_stats() or {}).get("bytes_in_use"))
                      for d in devs), file=log, flush=True)
    temp = max(int(searcher.lower(b).compile().memory_analysis()
                   .temp_size_in_bytes)
               for b in {searcher.buckets_for(size)[0] for size in warm})
    serving_bytes = held + temp
    phase("memory analysis")

    compiles = _CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if with_trace else None
    if clients == 1:
        cycles = traffic.cycles(mix, rng_traffic)
    else:
        # each client its own order of the same sizes
        cycles = [traffic.cycles(mix, np.random.default_rng([seed, 1, c]))
                  for c in range(clients)]
    setup_s = time.perf_counter() - t_start
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    compiles.on = True
    t_open = time.perf_counter()
    if clients == 1:
        records = loop.closed(serve, pool, cycles, seconds)
    else:
        records = loop.closed_pool(serve, pool, cycles, seconds)
    compiles.on = False
    if trace_dir:
        jax.profiler.stop_trace()
    replica_log = None if router is None else router.close()

    build_peak = _device_memory(devs, "peak_bytes_in_use")
    trace_rec = reduced = None
    if trace_dir:
        trace_rec = trace.record(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        reduced = trace.reduce(trace_rec, cell.chips)

    # the sample's answers and the coarse quantizer to the host, then the
    # program's state goes
    picks = _sample(records, rng_sample, int(mix["check"]))
    q_rows, ids, scores, rec = [], [], [], None
    for rec in picks:
        ids.append(np.asarray(rec["result"][1]))
        scores.append(np.asarray(rec["result"][0]))
        idx = (rec["offset"] + np.arange(rec["size"])) % pool.queries.shape[0]
        q_rows.append(pool.queries[idx])
    ivf = None if coarse is None else reference.ivf_table(*coarse, n)
    view = RunView(
        records=[{k: v for k, v in r.items() if k != "result"}
                 for r in records],
        n=n, d=d, row_bytes=row_bytes, device_kind=devs[0].device_kind,
        trace=trace_rec, reduced=reduced, replicas=replicas,
        replica_log=replica_log)
    del records, serve, searcher, router, picks, rec, setup, coarse
    gc.collect()
    phase("window and trace reading")

    checks, recall = {}, None
    if q_rows:
        q_rows, ids, scores = (np.concatenate(q_rows), np.concatenate(ids),
                               np.concatenate(scores))
        # one fixed sample shape per cell, so the reference compiles once
        real = q_rows.shape[0]
        width = max(int(mix["check"]) + max(warm), replicas * max(warm))
        q_pad = np.resize(q_rows, (width, d))
        id_pad = np.resize(ids, (width, ids.shape[1]))
        ref = reference.exact(gen, keys["corpus"], consts, n, cfg["metric"],
                              cfg["quant"], q_pad, id_pad, ivf=ivf,
                              nprobe=int(cfg["search"].get("nprobe", 0)))
        ref = {key: v[:real] if isinstance(v, np.ndarray) else v
               for key, v in ref.items()}
        numbers = reference.compare(ref, ids, scores)
        checks = {name: {"value": numbers[name], "limit": limit}
                  for name, limit in cfg["checks"].items()}
        recall = reference.recall(ref, ids)
        phase("reference")

    failed = sum(1 for r in view.records if r["done"] is None)
    correct = (bool(checks) and failed == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))

    if with_trace:
        metrics = {}
        for m in cell.per_layer:
            value = registry.reader(m["name"], cell.root)(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = end_to_end(view, t_open, recall, serving_bytes / n, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if e2e.get(m["name"]) is not None}

    # memory_peak_bytes is what the chip holds while it serves; the
    # build's transient peak, which is higher, is reported beside it
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": serving_bytes,
              "build_peak_bytes": build_peak}
    out = {"correct": correct, "attempted": len(view.records),
           "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["compiles_in_window"] = compiles.count
    out["checks"] = checks
    return out


def end_to_end(run: RunView, t_open: float, recall, hbm_per_row: float,
               setup_s: float) -> dict:
    """Every end-to-end metric this kind of loop has, from all requests."""
    out = {"recall_at_k": recall, "hbm_bytes_per_row": hbm_per_row,
           "setup_s": setup_s}
    done = [r for r in run.records if r["done"] is not None]
    if done:
        # all the work of the window over all its time: from the window's
        # opening to the last answer of a request sent in it
        span = max(r["done"] for r in done) - t_open
        out["qps"] = sum(r["size"] for r in done) / span
    return out
