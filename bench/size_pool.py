"""Size a cell's KV pool the way a deployment does, without the chip.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 bench/size_pool.py <workload>

Compiles the cell's fused decode tick and prefill step for one
described (not attached) TPU v5e and reads the compiler's memory
analysis.  The rule:

    pages: the most for which weights + pages * page_bytes
           + max(tick temps, prefill temps + prefill output)
           + margin <= hbm_limit

with ``margin`` 1 GiB for what the programs' analyses do not count
(the runtime's own reservations, the small programs of the first-token
path, fragmentation between the prefill's two pools).  The temporaries grow with the pool (the kernel's wrapper
relays the pool out), so they are fitted at two sizes and the rule
solved; the programs are compiled again at the answer.  Prints the pages and the seats a pool of that
size holds at the traffic's longest request.  Nothing runs and nothing
is timed.
"""
from __future__ import annotations

import argparse
import dataclasses
import math
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]

import arrivals  # noqa: E402

MARGIN = 1.0 * 2 ** 30


def analyse(cell, ref, pages: int, one_chip):
    import jax
    import jax.numpy as jnp
    import harness
    import weights as W
    from repro.kernels import ops
    from repro.models import model as M
    from repro.parallel.sharding import SINGLE_DEVICE_RULES
    ops._on_tpu = lambda: True            # lower the kernel for Mosaic
    cfg = harness.program_config(cell.config)
    s = cell.settings
    sh = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), t)
    dt = jnp.dtype(cell.config["weights_dtype"])
    params = jax.tree.map(
        lambda spec: jax.ShapeDtypeStruct(spec[0], dt, sharding=one_chip),
        W._nest(ref.shapes(cell.config["model"])),
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
    cache = sh(jax.eval_shape(lambda: M.init_paged_cache(
        cfg, pages, s["page_size"])))
    A, n = s["seats"], -(-s["max_seq_len"] // s["page_size"])
    vec = lambda d: jax.ShapeDtypeStruct((A,), d, sharding=one_chip)
    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    opts = M.RunOptions(q_chunk=min(s["max_seq_len"], 512))
    tick = jax.jit(
        lambda p, c, last, q, pt, nv, t, tk, tp, sd, rd, st:
            M.fused_decode_tick(p, cfg, c, last, q, pt, nv, t, tk, tp, sd,
                                rd, st, SINGLE_DEVICE_RULES, opts),
        donate_argnums=(1, 2, 3, 4, 11)).lower(
        params, cache, vec(i32), vec(i32),
        jax.ShapeDtypeStruct((A, n), i32, sharding=one_chip), vec(i32),
        vec(f32), vec(i32), vec(f32), vec(u32), vec(u32), vec(u32)).compile()
    C = s["prefill_chunk"]
    S = lambda shape: jax.ShapeDtypeStruct(shape, i32, sharding=one_chip)
    pre = jax.jit(lambda p, c, t, meta, pt: M.paged_decode_step(
        p, cfg, c, t, meta[:1], pt, meta[1:], SINGLE_DEVICE_RULES, opts)
    ).lower(params, cache, S((1, C)), S((2,)), S((1, n))).compile()
    return tick.memory_analysis(), pre.memory_analysis(), cfg


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--hbm-limit", type=float, default=15.75 * 2 ** 30,
                    help="bytes the TPU compiler lets one v5e program use")
    args = ap.parse_args()
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    import catalog
    from repro.models import model as M
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    cat = catalog.Catalog(ROOT)
    cell = cat.cell(args.workload)
    ref = cat.reference(cell.config)
    s = cell.settings
    itemsize = jax.numpy.dtype(cell.config["weights_dtype"]).itemsize
    wbytes = sum(math.prod(shape) for shape, _ in
                 ref.shapes(cell.config["model"]).values()) * itemsize
    import harness
    cfg = harness.program_config(cell.config)
    page_bytes = M.paged_page_bytes(cfg, s["page_size"])
    def temps(p):
        tick, pre, _ = analyse(cell, ref, p, one)
        t = (tick.temp_size_in_bytes,
             pre.temp_size_in_bytes + pre.output_size_in_bytes
             - pre.alias_size_in_bytes)
        print(f"pages {p}: tick temps {t[0]}, prefill temps + output {t[1]}")
        return t

    # both programs' temporaries grow linearly with the pool (the
    # kernel's wrapper relays the pool out): fit them at two sizes
    p1, p2 = 256, 512
    (a1, b1), (a2, b2) = temps(p1), temps(p2)
    best = 0
    for t1, t2 in ((a1, a2), (b1, b2)):
        slope = (t2 - t1) / (p2 - p1)
        base = t1 - slope * p1
        p = int((args.hbm_limit - wbytes - base - MARGIN)
                // (page_bytes + slope))
        best = p if best == 0 else min(best, p)
    pages = best
    t = temps(pages)
    used = wbytes + max(t) + pages * page_bytes
    print(f"weights {wbytes} B, page {page_bytes} B: {pages} pages "
          f"({pages * page_bytes} B); weights + pool + temps {used} B of "
          f"{int(args.hbm_limit)} B")
    usable = pages - 1                   # page 0 is the scratch page
    if cell.traffic["kind"] == "replay":
        need = sum(-(-(int(r["prompt"]) + int(r["output"])) // s["page_size"])
                   for r in cell.traffic["requests"])
        print(f"{args.workload}: num_pages {pages}; the replay needs "
              f"{need} pages at most")
        return
    longest = -(-arrivals.longest_total(cell.traffic) // s["page_size"])
    print(f"{args.workload}: num_pages {pages}; {usable // longest} seats "
          f"at the longest request ({longest} pages)")


if __name__ == "__main__":
    main()
