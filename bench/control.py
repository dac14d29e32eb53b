#!/usr/bin/env python3
"""The control of a cell's correctness check: the plain reference in the
program's place, computed one precision lower (bfloat16 for the float32 the
configurations state), judged by the same comparison as a benchmark run.

    python3 bench/control.py --workload fleet_1e7.diurnal_horizon --seeds 1 2 3 --steps 400

``--steps`` is how much work one seed does: rounds for a horizon cell, ticks
(spread over the tenants as the mix's rates spread them) for a served cell;
give what a run of the cell does.  Prints one JSON line per seed with the
compared numbers beside the cell's limits; the control has to fail at least
one of them.  Benchmark runs never run this; it sets the upper readings the
limits are chosen under (``PERF.md``).  Needs the chip; the tests call
``control()`` at CPU sizes.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))


def horizon_control(reg, config, mix, seed: int, steps: int, lower) -> list:
    """The bf16 reference's free run compared with the float32 one."""
    import jax
    import jax.numpy as jnp

    from benchkit.availability import diurnal_pool
    from benchkit.device import seed_key

    driver = reg.driver(mix["driver"])
    ref = reg.reference(config["reference"])
    fl = config["fl"]
    K, k, C = fl["K"], fl["k"], int(mix["chunk_rounds"])
    n = -(-steps // C) * C
    key0 = seed_key(seed, driver.STREAM_ENGINE)
    pool = diurnal_pool(seed_key(seed, driver.STREAM_TRAFFIC), K, C, mix)
    out = {}
    for name, dt in (("reference", jnp.float32), ("control", lower)):
        spec = ref.RoundSpec(K, k, fl["quota_frac"], fl["eta"], dtype=dt)
        st, _, succ, _ = ref.free_run(spec, ref.init_state(spec), key0, pool, n, C)
        out[name] = (jax.tree.map(np.asarray, st), np.asarray(succ))
    cst, csucc = out["control"]
    return driver.compare_horizon(
        k=k, rounds=n, prog_counts=cst["sel_counts"], prog_logw=cst["logw"].astype(np.float32),
        prog_t=int(cst["t"]), prog_succ=csucc, ref_state=out["reference"][0], ref_succ=out["reference"][1],
        limits=config["limits"],
    )


def serve_control(reg, config, mix, seed: int, steps: int, lower) -> list:
    """Per tenant, the bf16 reference picks its own cohorts; the comparison
    of ``drivers/serve.py`` then judges them and the bf16 log-weights, as it
    judges a server's answers."""
    from benchkit.device import sub_seed
    from benchkit.loadgen import payload_rows
    from benchkit.schedule import expand_tenants, zipf_shares

    driver = reg.driver(mix["driver"])
    ref = reg.reference(config["reference"])
    args = config.get("reference_args", {})
    tenants = expand_tenants(config["tenants"])
    shares = zipf_shares(len(tenants), mix["zipf_s"]) if mix["loop"] == "open" else np.full(len(tenants), 1.0)
    gap, logw_gap = 0.0, 0.0
    P = int(mix["payload_rows"])
    for t, share in zip(tenants, shares):
        n = max(1, int(round(steps * share)))
        t["seed"] = sub_seed(seed, driver.STREAM_JOBS, t["index"])
        t["bits_seed"] = [seed, driver.STREAM_BITS, t["index"]]
        t["classes"] = mix["classes"]
        job, bits, rows = driver.job_spec(t), payload_rows(t, P), [r % P for r in range(n)]
        cohorts, clogw = ref.replay_job(job, bits, rows, None, dtype=lower, **args)
        g, lg = driver.compare_job(ref, job, bits, rows, cohorts, clogw, args)
        gap, logw_gap = max(gap, g), max(logw_gap, lg)
    return driver.checks(config["limits"], gap, logw_gap, 0, 0)


def control(reg, workload: str, seed: int, steps: int) -> list:
    cell = reg.cell(workload)
    config, mix = reg.config(cell["config"]), reg.traffic(cell["traffic"])
    if mix["driver"] == "horizon":
        import jax.numpy as jnp

        return horizon_control(reg, config, mix, seed, steps, jnp.bfloat16)
    return serve_control(reg, config, mix, seed, steps, "bfloat16")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--steps", type=int, required=True)
    args = ap.parse_args(argv)

    from benchkit.registry import ROOT, Registry

    sys.path.insert(0, str(ROOT / "src"))
    reg = Registry(ROOT)
    from benchkit.device import enable_compile_cache, require_chips

    require_chips(int(reg.cell(args.workload)["chips"]))
    enable_compile_cache()
    for seed in args.seeds:
        checks = control(reg, args.workload, seed, args.steps)
        failed = [c["name"] for c in checks if c["value"] > c["limit"]]
        print(json.dumps({"workload": args.workload, "seed": seed, "steps": args.steps, "control_fails": failed,
                          "checks": {c["name"]: {"value": c["value"], "limit": c["limit"]} for c in checks}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
