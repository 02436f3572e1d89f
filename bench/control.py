"""The control: the reference put in the Searcher's place, one precision
below the configuration's (int4 codes for an int8 configuration).

It runs a cell as ``bench/run.py`` does, with the window driving an exact
search over the reference's own lower-precision Eq. 1 codes (for an ivf
configuration, over the rows of each query's probed lists, picked as the
reference picks them), and prints the numbers ``correct`` compares.  The
check has to find it not correct.  With ``--fault`` the window drives the
program with one of ``bench/faults.py``'s faults planted instead.  The
benchmark's own runs never run either.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 3
    python3 bench/control.py --workload <cell> --seeds 11,12,13 --fault half_probe
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from functools import partial  # noqa: E402

if __name__ == "__main__":
    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import data, faults, reference  # noqa: E402

#: the precision the control drops to, for each configured code width
LOWER_BITS = {8: 4}


@dataclasses.dataclass
class Answer:
    scores: jax.Array
    ids: jax.Array
    stats: dict


@partial(jax.jit, static_argnums=2, donate_argnums=0)
def _put_codes(out, x, bits, fit, start):
    codes = reference.eq1(x, fit, bits).astype(jnp.int8)
    return jax.lax.dynamic_update_slice_in_dim(out, codes, start, 0)


def _encode(gen, key, n, consts, bits, fit):
    size = data.block_rows(n)
    out = jnp.zeros((n, gen.d), jnp.int8)
    for b in range(n // size):
        out = _put_codes(out, data.block(gen, key, b, size, consts), bits,
                         fit, b * size)
    return out


@partial(jax.jit, static_argnames=("k", "metric", "bits"))
def _search(codes, q, fit, probe, *, k, metric, bits):
    n = codes.shape[0]
    block = data.block_rows(n)
    qc = reference.eq1(q, fit, bits)
    cols = jnp.arange(block, dtype=jnp.int32)

    def body(b, carry):
        top_s, top_i = carry
        xc = jax.lax.dynamic_slice_in_dim(codes, b * block, block)
        s = reference.int_scores(qc, xc.astype(jnp.int32), metric)
        if probe is not None:
            row_list, probed = probe
            may = jnp.take(probed, jax.lax.dynamic_slice_in_dim(
                row_list, b * block, block), axis=1)
            s = jnp.where(may, s, reference.INT_MIN)
        cand_i = jnp.broadcast_to(b * block + cols, s.shape)
        vals, pos = jax.lax.top_k(jnp.concatenate([top_s, s], axis=1), k)
        ids = jnp.take_along_axis(jnp.concatenate([top_i, cand_i], axis=1),
                                  pos, axis=1)
        return vals, ids

    Q = q.shape[0]
    init = (jnp.full((Q, k), reference.INT_MIN, jnp.int32),
            jnp.full((Q, k), -1, jnp.int32))
    s, i = jax.lax.fori_loop(0, n // block, body, init)
    return s.astype(jnp.float32), i


class LowerPrecision:
    """Exact top-k over the reference's Eq. 1 codes at ``bits`` bits."""

    def __init__(self, setup: dict, bits: int):
        cell = setup["cell"]
        cfg = cell.config
        self.n = int(cfg["n"])
        self.k = int(cell.mix["k"])
        self.metric = cfg["metric"]
        self.bits = bits
        gen, consts, key = setup["gen"], setup["consts"], setup["keys"]["corpus"]
        self.fit = reference.eq1_fit(gen, key, self.n, consts, cfg["quant"])
        self.codes = _encode(gen, key, self.n, consts, bits, self.fit)
        self.ivf = None
        if setup["coarse"] is not None:
            self.ivf = reference.ivf_table(*setup["coarse"], self.n)
            self.row_list = jnp.asarray(self.ivf["row_list"])
            self.nprobe = int(cfg["search"]["nprobe"])

    def __call__(self, queries) -> Answer:
        q = jnp.asarray(queries, jnp.float32)
        probe = None
        if self.ivf is not None:
            probe = (self.row_list, jnp.asarray(reference.probe_lists(
                queries, self.ivf["centroids"], self.nprobe,
                self.metric)["exact"]))
        s, i = _search(self.codes, q, self.fit, probe, k=self.k,
                       metric=self.metric, bits=self.bits)
        return Answer(s, i, {"bucket": int(q.shape[0]), "padded_q": 0})


def system(cell):
    """The ``system`` hook of ``harness.run_cell`` that runs the control."""
    bits = LOWER_BITS[int(cell.config["quant"]["bits"])]
    return lambda _searcher, setup: LowerPrecision(setup, bits)


def main(argv=None) -> int:
    from bench import harness, registry

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--fault", choices=sorted(faults.BY_NAME),
                    help="plant this fault in the program instead")
    args = ap.parse_args(argv)
    cell = registry.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        hook = (faults.BY_NAME[args.fault] if args.fault else system(cell))
        try:
            out = harness.run_cell(cell, seed, args.seconds, False,
                                   t_start=time.perf_counter(), system=hook)
        except harness.NoChip as e:
            print(f"[control] no chip: {e}", file=sys.stderr)
            return 1
        print(json.dumps({"control": args.workload, "seed": seed,
                          "fault": args.fault,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
