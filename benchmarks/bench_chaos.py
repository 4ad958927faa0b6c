"""Chaos benchmark: per-scenario degradation envelopes, tracked as
``BENCH_chaos.json`` — graceful degradation as a measured, CI-gated
property.

Sections:

* ``parity`` — the fault-free chaos run (an *empty* ``FaultPlane`` attached,
  interposition active) against the plain no-plane path under identical
  deterministic stage costs: hybrid-RMSE delta must be <= 1e-6 and train
  dispatch counts identical, so the fault plane itself is proven to be a
  no-op when no faults fire.
* ``scenarios`` — every scenario in ``core.scenarios.SCENARIOS`` under one
  fixed seed: RMSE ratio vs fault-free, p99 answer latency, max served
  staleness, fallback fraction, fault/recovery counters, zero unhandled
  exceptions.  Scenario-specific gates: corrupted publishes detected 100%
  and never installed; *forged* publishes (valid crc32, no valid HMAC)
  rejected 100% by the signed-sync verifier; partitioned sync keeps served
  staleness within the watchdog bound and hybrid RMSE <= 1.5x fault-free.
* ``health`` — the self-diagnosing health plane's own envelope: zero false
  positives on the fault-free run (no suspicions, no Byzantine flags, no
  signature rejections, no threshold adaptations), and partition/crash
  detection latency within 2 heartbeat intervals of fault onset.
* ``adaptive`` — the adaptive-threshold path against a static-threshold
  plane on the fault-free run: byte-identical bus log, ledger, and
  forecasts (adaptation must cost nothing when calm); a faulty run must
  record at least one threshold adaptation.
* ``determinism`` — EVERY scenario rerun under the same seed must produce
  byte-identical bus logs, ledgers, and forecasts; a different seed must
  produce a different fault schedule.

    PYTHONPATH=src python -m benchmarks.bench_chaos            # full
    PYTHONPATH=src python -m benchmarks.bench_chaos --smoke    # CI
"""
from __future__ import annotations

import argparse
import json
from typing import Dict

SEED = 0


def run(smoke: bool) -> Dict:
    from repro.core.scenarios import (
        RMSE_RATIO_MAX,
        SCENARIOS,
        ChaosHarness,
        bus_signature,
        forecast_signature,
        ledger_signature,
    )

    if smoke:
        h = ChaosHarness(n_streams=2, n_windows=4, records_per_window=80,
                         qps=6.0, verbose=True)
    else:
        h = ChaosHarness(n_streams=3, n_windows=6, records_per_window=120,
                         qps=8.0, verbose=True)

    out: Dict = {"config": {
        "smoke": smoke, "seed": SEED, "n_streams": h.n_streams,
        "n_windows": h.n_windows, "records_per_window": h.rpw,
        "period_s": h.period, "qps": h.qps,
        "staleness_bound": h.staleness_bound,
    }}

    def sigs(res):
        return (bus_signature(res), ledger_signature(res),
                forecast_signature(res))

    # -- parity: empty fault plane == no fault plane -------------------------
    print("parity: plain (no plane) vs fault_free (empty plane) ...")
    plain = h.run_plain()
    env_ff, res_ff = h.run_scenario("fault_free", seed=SEED)
    assert res_ff is not None, env_ff
    rmse_plain = plain.mean_rmse()["hybrid"]
    out["parity"] = {
        "rmse_plain": rmse_plain,
        "rmse_fault_free": env_ff["rmse_hybrid"],
        "rmse_abs_delta": abs(rmse_plain - env_ff["rmse_hybrid"]),
        "train_dispatches_plain": plain.train_dispatches,
        "train_dispatches_fault_free": env_ff["train_dispatches"],
        "forecasts_identical": (forecast_signature(plain)
                                == forecast_signature(res_ff)),
    }

    # -- the scenario envelopes ----------------------------------------------
    base = env_ff["rmse_hybrid"]
    out["scenarios"] = {}
    first_sigs: Dict[str, tuple] = {}
    for name in SCENARIOS:
        print(f"scenario: {name} ...")
        env, res = h.run_scenario(name, seed=SEED)
        env["rmse_ratio_vs_fault_free"] = (
            env.get("rmse_hybrid", float("inf")) / base if base else
            float("inf"))
        env["rmse_ratio_max"] = RMSE_RATIO_MAX[name]
        if name == "corrupted_int8_sync" and res is not None:
            stats = env["fault_stats"]
            env["corrupt_injected"] = stats.get("msg_corrupt", 0)
            env["corrupt_detected_frac"] = (
                env["corrupt_rejected"] / env["corrupt_injected"]
                if env["corrupt_injected"] else 1.0)
        if name == "forged_sync" and res is not None:
            stats = env["fault_stats"]
            env["forged_injected"] = stats.get("msg_forge", 0)
            env["forged_detected_frac"] = (
                env["forged_rejected"] / env["forged_injected"]
                if env["forged_injected"] else 1.0)
        if res is not None:
            first_sigs[name] = sigs(res)
        out["scenarios"][name] = env

    # -- health plane: false-positive floor + detection latency --------------
    hff = out["scenarios"]["fault_free"].get("health", {})
    out["health"] = {
        "fault_free_suspicions": hff.get("n_suspected", -1),
        "fault_free_byz_flagged": hff.get("byz_flagged", -1),
        "fault_free_forged_rejected": out["scenarios"]["fault_free"].get(
            "forged_rejected", -1),
        "fault_free_threshold_adaptations": hff.get(
            "threshold_adaptations", -1),
        "detection": {},
    }
    for name in ("partitioned_sync", "site_crash"):
        hs = out["scenarios"][name].get("health", {})
        out["health"]["detection"][name] = {
            "latency_s": hs.get("detection_latency_s"),
            "latency_hb_intervals": hs.get("detection_latency_hb_intervals"),
            "n_recovered": hs.get("n_recovered", 0),
        }

    # -- adaptive thresholds: free when calm, engaged under faults -----------
    print("adaptive: fault_free under static thresholds ...")
    _, r_static = h.run_scenario("fault_free", seed=SEED, adaptive=False)
    st = sigs(r_static)
    out["adaptive"] = {
        "calm_bus_identical": first_sigs["fault_free"][0] == st[0],
        "calm_ledger_identical": first_sigs["fault_free"][1] == st[1],
        "calm_forecasts_identical": first_sigs["fault_free"][2] == st[2],
        "faulty_threshold_adaptations": out["scenarios"][
            "partitioned_sync"].get("health", {}).get(
                "threshold_adaptations", 0),
    }

    # -- determinism: same seed -> byte-identical, every scenario ------------
    out["determinism"] = {"per_scenario": {}}
    for name in SCENARIOS:
        print(f"determinism: {name} rerun ...")
        _, r2 = h.run_scenario(name, seed=SEED)
        s1, s2 = first_sigs[name], sigs(r2)
        out["determinism"]["per_scenario"][name] = {
            "bus_log_identical": s1[0] == s2[0],
            "ledger_identical": s1[1] == s2[1],
            "forecasts_identical": s1[2] == s2[2],
        }
    print("determinism: sensor_chaos under a different seed ...")
    _, r3 = h.run_scenario("sensor_chaos", seed=SEED + 7)
    out["determinism"]["different_seed_differs"] = (
        first_sigs["sensor_chaos"][0] != bus_signature(r3))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (fewer streams/windows)")
    ap.add_argument("--out", default="BENCH_chaos.json")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    res = run(args.smoke)
    with open(args.out, "w") as f:
        json.dump(res, f, indent=2, sort_keys=True)
    print(f"\nwrote {args.out}")

    p = res["parity"]
    print(f"parity: rmse delta {p['rmse_abs_delta']:.2e}, dispatches "
          f"{p['train_dispatches_fault_free']}=="
          f"{p['train_dispatches_plain']}, forecasts identical: "
          f"{p['forecasts_identical']}")
    for name, env in res["scenarios"].items():
        if env.get("unhandled_exception"):
            print(f"{name:>20}: EXCEPTION {env['unhandled_exception']}")
            continue
        print(f"{name:>20}: rmse x{env['rmse_ratio_vs_fault_free']:.3f} "
              f"(max {env['rmse_ratio_max']}), "
              f"p99 {env['p99_latency_s']*1e3:.1f}ms, "
              f"stale<= {env['max_staleness']}, "
              f"fallback {env['fallback_frac']:.2f}, "
              f"answered {env['n_answered']} (starved {env['n_starved']})")
    h = res["health"]
    print(f"health: fault-free FPs {h['fault_free_suspicions']} suspicions/"
          f"{h['fault_free_byz_flagged']} byz flags/"
          f"{h['fault_free_forged_rejected']} sig rejects, "
          + ", ".join(
              f"{n} detected in {det['latency_hb_intervals']:.2f} hb "
              f"intervals" for n, det in h["detection"].items()
              if det["latency_hb_intervals"] is not None))
    fg = res["scenarios"]["forged_sync"]
    print(f"forged sync: {fg['forged_rejected']}/{fg['forged_injected']} "
          f"rejected by HMAC (checksum alone accepted all of them)")
    a = res["adaptive"]
    print(f"adaptive: calm run identical to static thresholds "
          f"{a['calm_bus_identical'] and a['calm_ledger_identical'] and a['calm_forecasts_identical']}, "
          f"{a['faulty_threshold_adaptations']} adaptation(s) under the "
          f"partition")
    d = res["determinism"]
    ok = all(all(s.values()) for s in d["per_scenario"].values())
    print(f"determinism: all {len(d['per_scenario'])} scenarios rerun "
          f"byte-identical: {ok}, seed-sensitivity "
          f"{d['different_seed_differs']}")


if __name__ == "__main__":
    main()
