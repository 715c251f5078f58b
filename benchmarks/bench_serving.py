"""Serving-service benchmark: open-loop Poisson traffic vs the
fault-tolerant ``SimulationService``.

Three phases per tenant level (1 / 8 / 64 concurrent tenants):

  healthy    no injection — baseline p50/p99 latency and clips/sec,
  faulted    ~10% injected faults split across every chaos kind
             (device errors, NaN outputs, slow flushes, corrupt RT-store
             reads, mid-persist crashes) on the REAL serving path,
  recovery   injection off again — the service must climb the ladder
             back to the fused+int8 top tier (exponential backoff).

The driver is open-loop: each tenant submits on its own Poisson arrival
schedule regardless of completions, so overload shows up as typed
``overloaded``/``deadline_exceeded`` results, not as a stalled driver.

Gates (enforced here, read by the CI chaos leg):

  typed       every submitted request resolves to a typed result — no
              hang, no silent drop, in every phase including faulted,
  gated       every successful result in the faulted phase stays within
              the int8 rel-err gate vs the monolithic fp32 reference
              (the loosest rung of the ladder: 5% at bench scale
              d_model=64, 1% at the paper scale) — degradation never
              ships an ungated wrong answer,
  repromoted  after faults stop the service serves from the top tier
              again,
  p99         healthy-phase p99 latency at 1 tenant under a generous
              absolute bound (shared-CI-runner safe).
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
import urllib.request
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

if __package__ in (None, ""):   # direct `python benchmarks/bench_serving.py`
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.common import (SERVING_BENCH_SCHEMA_VERSION, bench_cfg,
                               full_cfg, get_mixed_dataset)
from repro.core import predictor
from repro.core.engine_config import EngineConfig, ObservabilityConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.engine import PredictorEngine, Request
from repro.serving.service import (TIER_TRANSITIONS_TOTAL, ServiceSLA,
                                   SimulationService)

# ~10% total injected fault probability per opportunity, split evenly
# across every chaos kind the stack supports
FAULT_MIX_10PCT = {"device_error": 0.02, "nan_output": 0.02,
                   "slow_flush": 0.02, "corrupt_rt_read": 0.02,
                   "crash_persist": 0.02}


def _percentile(xs: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if xs else float("nan")


_PROM_LINE = re.compile(r'^(\w+)\{(.*)\} (\S+)$')
_PROM_LABEL = re.compile(r'(\w+)="([^"]*)"')


def scrape_transitions(port: int, instance: str) -> List[Dict]:
    """GET /metrics and parse this service's tier-transition counter
    series — the same scrape a production Prometheus would do, driven
    mid-bench so the exporter path is exercised under live traffic."""
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
        text = resp.read().decode()
    rows = []
    for line in text.splitlines():
        m = _PROM_LINE.match(line)
        if not m or m.group(1) != TIER_TRANSITIONS_TOTAL:
            continue
        labels = dict(_PROM_LABEL.findall(m.group(2)))
        if labels.get("instance") != instance:
            continue
        labels["count"] = int(float(m.group(3)))
        rows.append(labels)
    return rows


def transition_gates(probe: List[Dict], stats: Dict,
                     flight_last: Optional[Dict]) -> Dict:
    """Cross-check the three independent transition ledgers: the scraped
    counter series, the snapshot's per-tier counters, and the flight
    recorder's event ring (when a postmortem was taken).

    Every demotion recorded on a non-floor tier produced exactly one
    transition (floor trips have nowhere to go); every promotion
    produced one.  All three ledgers must agree on those totals.
    """
    tiers = stats["tiers"]
    names = list(tiers)
    exp_demote = sum(tiers[n]["demotions"] for n in names[:-1])
    exp_promote = sum(tiers[n]["promotions"] for n in names)
    got_demote = sum(r["count"] for r in probe
                     if r["reason"] != "promotion")
    got_promote = sum(r["count"] for r in probe
                      if r["reason"] == "promotion")
    out = {
        "expected_demote_transitions": exp_demote,
        "expected_promote_transitions": exp_promote,
        "probed_demote_transitions": got_demote,
        "probed_promote_transitions": got_promote,
        "metrics_consistent": (got_demote == exp_demote
                               and got_promote == exp_promote),
    }
    if flight_last is not None:
        # the postmortem freezes (events, state) atomically inside
        # _demote, so ITS ledgers must agree with each other too
        ev = [e for e in flight_last["events"]
              if e["kind"] == "tier_transition"]
        ptiers = flight_last["state"]["tiers"]
        pnames = list(ptiers)
        p_exp_dem = sum(ptiers[n]["demotions"] for n in pnames[:-1])
        p_exp_pro = sum(ptiers[n]["promotions"] for n in pnames)
        f_dem = sum(1 for e in ev if e["reason"] != "promotion")
        f_pro = sum(1 for e in ev if e["reason"] == "promotion")
        out["flight_demote_events"] = f_dem
        out["flight_promote_events"] = f_pro
        out["flight_consistent"] = (f_dem == p_exp_dem
                                    and f_pro == p_exp_pro)
    else:
        out["flight_consistent"] = None      # no demotion, nothing to dump
    return out


def make_requests(ds, n_requests: int, clips_per_req: int, id0: int
                  ) -> List[Request]:
    """Slice the dataset's clip pool into request payloads (wrapping)."""
    reqs = []
    for i in range(n_requests):
        lo = (i * clips_per_req) % max(len(ds) - clips_per_req, 1)
        hi = lo + clips_per_req
        reqs.append(Request(id0 + i, ds.clip_tokens[lo:hi],
                            ds.context_tokens[lo:hi], ds.clip_mask[lo:hi]))
    return reqs


def reference_totals(params, cfg, config: EngineConfig,
                     reqs: List[Request]) -> Dict[int, float]:
    """Monolithic fp32 totals per request id — the trusted answer the
    faulted phase's successful results are gated against.  Callers pass
    a bounded sample: the monolithic path is the slow rung by design
    (that is the whole point of the ladder), so gating every full-scale
    request here would dwarf the bench itself."""
    eng = PredictorEngine(params, cfg, config.replace(
        precision=None, fused_serving=False, rt_cache=False,
        rt_store_dir=None, faults=()))
    for r in reqs:
        eng.submit(r)
    return {r.request_id: r.total_cycles for r in eng.flush()}


def drive_phase(svc: SimulationService, reqs: List[Request],
                n_tenants: int, mean_gap_s: float, deadline_s: float,
                rng: np.random.Generator
                ) -> Tuple[List, List[float], float]:
    """Open-loop Poisson driver: merge the tenants' exponential arrival
    schedules and submit on the clock.  Returns (results, client-side
    latencies of successful requests, wall seconds)."""
    per_tenant = max(1, len(reqs) // n_tenants)
    arrivals = []                                  # (t, req)
    k = 0
    for _ in range(n_tenants):
        t = 0.0
        for _ in range(per_tenant):
            if k >= len(reqs):
                break
            t += float(rng.exponential(mean_gap_s))
            arrivals.append((t, reqs[k]))
            k += 1
    arrivals.sort(key=lambda a: a[0])

    t0 = time.time()
    submitted = []                                 # (ticket, t_submit)
    for t_at, req in arrivals:
        now = time.time() - t0
        if t_at > now:
            time.sleep(t_at - now)
        submitted.append((svc.submit(req, deadline_s=deadline_s),
                          time.time()))
    results, latencies = [], []
    for ticket, t_sub in submitted:
        # typed-result contract: generous absolute cap, never a hang
        res = ticket.result(timeout=deadline_s + 600)
        results.append(res)
        if res.ok:
            latencies.append(time.time() - t_sub if not res.latency_seconds
                             else res.latency_seconds)
    return results, latencies, time.time() - t0


def settle_to_top(svc: SimulationService, reqs: List[Request],
                  deadline_s: float, max_extra: int = 60) -> int:
    """Trickle requests one at a time until the service re-promotes to
    the top tier (bounded).  Returns how many it took."""
    top = svc.tier_stats[0].name
    for i in range(max_extra):
        if svc.current_tier == top:
            return i
        r = reqs[i % len(reqs)]
        svc.submit(Request(10_000_000 + i, r.clip_tokens,
                           r.context_tokens, r.clip_mask),
                   deadline_s=deadline_s).result(timeout=deadline_s + 600)
    return max_extra


def phase_block(results, latencies, wall: float, svc) -> Dict:
    statuses: Dict[str, int] = {}
    for r in results:
        statuses[r.status] = statuses.get(r.status, 0) + 1
    ok_clips = sum(r.n_clips for r in results if r.ok)
    return {
        "n_requests": len(results),
        "statuses": statuses,
        "p50_s": _percentile(latencies, 50),
        "p99_s": _percentile(latencies, 99),
        "clips_per_s": ok_clips / max(wall, 1e-9),
        "wall_s": wall,
        "tier_end": svc.current_tier,
    }


def run_level(params, cfg, ds, n_tenants: int, *, quick: bool,
              rel_err_gate: float, seed: int,
              metrics_port: Optional[int] = None,
              flight_dir: Optional[str] = None,
              trace_out: Optional[str] = None) -> Dict:
    per_req = 8 if quick else 16
    n_req = n_tenants * (4 if quick else 6)
    mean_gap = 0.25 if quick else 0.1
    deadline = 30.0 if quick else 120.0
    obs_cfg = None
    if flight_dir or trace_out:
        obs_cfg = ObservabilityConfig(trace=bool(trace_out),
                                      flight_dir=flight_dir)
    config = EngineConfig(
        batch_size=32 if quick else 64, l_clip=64, l_token=16,
        faults=FAULT_MIX_10PCT, fault_seed=seed,
        observability=obs_cfg)
    sla = ServiceSLA(queue_limit=max(64, 2 * n_req),
                     default_deadline_s=deadline,
                     watchdog_s=15.0 if quick else 45.0,
                     promote_after=2, backoff_max=8)
    rng = np.random.default_rng(seed)

    level: Dict = {"n_tenants": n_tenants}
    with SimulationService(params, cfg, config, sla=sla) as svc:
        base = n_tenants * 1_000_000
        all_reqs = make_requests(ds, 3 * n_req, per_req, base)
        h_reqs, f_reqs, r_reqs = (all_reqs[:n_req],
                                  all_reqs[n_req:2 * n_req],
                                  all_reqs[2 * n_req:])
        # gate sample: only faulted-phase results are rel-err gated, and
        # only a bounded prefix of them is worth a monolithic replay
        ref = reference_totals(params, cfg, config,
                               f_reqs[: 24 if quick else 32])
        svc.prewarm(Request(base - 1, h_reqs[0].clip_tokens[:2],
                            h_reqs[0].context_tokens[:2],
                            h_reqs[0].clip_mask[:2]))

        svc.injector.set_enabled(False)
        res_h, lat_h, wall_h = drive_phase(svc, h_reqs, n_tenants,
                                           mean_gap, deadline, rng)
        level["healthy"] = phase_block(res_h, lat_h, wall_h, svc)

        svc.injector.set_enabled(True)
        res_f, lat_f, wall_f = drive_phase(svc, f_reqs, n_tenants,
                                           mean_gap, deadline, rng)
        level["faulted"] = phase_block(res_f, lat_f, wall_f, svc)
        level["faults_fired"] = svc.injector.stats()
        if metrics_port is not None:
            # live scrape between phases: the exporter serves while the
            # service is still taking traffic
            level["metrics_probe_mid"] = scrape_transitions(
                metrics_port, svc.instance)

        svc.injector.set_enabled(False)
        res_r, lat_r, wall_r = drive_phase(svc, r_reqs, n_tenants,
                                           mean_gap, deadline, rng)
        extra = settle_to_top(svc, r_reqs, deadline)
        level["recovery"] = phase_block(res_r, lat_r, wall_r, svc)
        level["recovery"]["settle_requests"] = extra

        # gates -----------------------------------------------------------
        every = res_h + res_f + res_r
        typed = all(r.status in ("ok", "degraded", "overloaded",
                                 "deadline_exceeded", "failed")
                    for r in every) and len(every) == 3 * n_req
        worst_rel = 0.0
        for r in res_f:
            if r.ok and ref.get(r.request_id):
                worst_rel = max(worst_rel,
                                abs(r.total_cycles - ref[r.request_id])
                                / abs(ref[r.request_id]))
        level["gates"] = {
            "typed": typed,
            "n_ref_sampled": len(ref),
            "worst_faulted_rel_err": worst_rel,
            "gated": worst_rel <= rel_err_gate,
            "repromoted": svc.current_tier == svc.tier_stats[0].name,
        }
        level["stats"] = svc.stats()
        if metrics_port is not None:
            probe = scrape_transitions(metrics_port, svc.instance)
            level["metrics_probe"] = probe
            flight_last = (svc.obs.flight.last
                           if svc.obs.flight is not None else None)
            level["gates"].update(transition_gates(
                probe, level["stats"], flight_last))
        if svc.obs.flight is not None:
            level["postmortems"] = list(svc.obs.flight.postmortems)
    if trace_out:
        svc.obs.tracer.dump(trace_out)
    return level


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: bench-scale model (d_model=64), "
                         "tenant levels 1/8, int8 gate 5%%")
    ap.add_argument("--tenants", type=int, nargs="*", default=None,
                    help="override the tenant levels (default 1 8 64; "
                         "--quick default 1 8)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the schema-stamped breakdown artifact")
    ap.add_argument("--metrics-port", type=int, default=0, metavar="PORT",
                    help="serve /metrics for the run and probe it "
                         "between phases (0 = ephemeral port; the "
                         "tier-transition consistency gates always run)")
    ap.add_argument("--no-metrics", action="store_true",
                    help="skip the exporter + probe + consistency gates")
    ap.add_argument("--flight-dir", default=None, metavar="DIR",
                    help="flight-recorder postmortem directory: every "
                         "demotion dumps events + spans + metrics + the "
                         "service snapshot as JSON")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable span tracing; dump the last level's "
                         "Chrome/Perfetto trace JSON here")
    args = ap.parse_args()
    enable_compile_cache()

    quick = args.quick
    levels = args.tenants or ([1, 8] if quick else [1, 8, 64])
    cfg = bench_cfg() if quick else full_cfg()
    rel_err_gate = 0.05 if quick else 0.01
    params = predictor.init_params(cfg, jax.random.PRNGKey(0))
    ds = get_mixed_dataset(4 if quick else 8)

    metrics_port = None
    metrics_server = None
    if not args.no_metrics:
        from repro.obs.exporter import serve_metrics
        metrics_server = serve_metrics(port=args.metrics_port)
        metrics_port = metrics_server.server_address[1]
        print(f"metrics: http://127.0.0.1:{metrics_port}/metrics")

    out = {"schema_version": SERVING_BENCH_SCHEMA_VERSION,
           "quick": quick, "rel_err_gate": rel_err_gate,
           "metrics_port": metrics_port, "levels": []}
    ok = True
    for n in levels:
        print(f"== {n} tenant(s) ==")
        level = run_level(params, cfg, ds, n, quick=quick,
                          rel_err_gate=rel_err_gate, seed=args.seed,
                          metrics_port=metrics_port,
                          flight_dir=args.flight_dir,
                          trace_out=args.trace_out)
        out["levels"].append(level)
        for ph in ("healthy", "faulted", "recovery"):
            b = level[ph]
            print(f"  {ph:9s} p50={b['p50_s']:6.2f}s p99={b['p99_s']:6.2f}s "
                  f"{b['clips_per_s']:7.1f} clips/s {b['statuses']} "
                  f"tier_end={b['tier_end']}")
        print(f"  faults fired: {level['faults_fired']}")
        g = level["gates"]
        print(f"  gates: typed={g['typed']} gated={g['gated']} "
              f"(worst rel err {g['worst_faulted_rel_err']:.2e} <= "
              f"{rel_err_gate}) repromoted={g['repromoted']}")
        ok = ok and g["typed"] and g["gated"] and g["repromoted"]
        if "metrics_consistent" in g:
            print(f"  ledgers: metrics_consistent="
                  f"{g['metrics_consistent']} "
                  f"(demote {g['probed_demote_transitions']}/"
                  f"{g['expected_demote_transitions']}, promote "
                  f"{g['probed_promote_transitions']}/"
                  f"{g['expected_promote_transitions']}) "
                  f"flight_consistent={g['flight_consistent']}")
            ok = ok and g["metrics_consistent"] \
                and g["flight_consistent"] is not False

    # the 1-tenant healthy p99 bound: generous, absolute, runner-safe
    p99_bound = 20.0 if quick else 60.0
    p99 = out["levels"][0]["healthy"]["p99_s"]
    out["p99_bound_s"] = p99_bound
    out["gates_pass"] = bool(ok and p99 <= p99_bound)
    print(f"1-tenant healthy p99 {p99:.2f}s (bound {p99_bound}s); "
          f"all gates {'PASS' if out['gates_pass'] else 'FAIL'}")
    if metrics_server is not None:
        metrics_server.shutdown()
    if args.json:
        Path(args.json).write_text(json.dumps(out, indent=2))
        print(f"wrote {args.json}")
    if not out["gates_pass"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
