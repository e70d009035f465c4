"""Benchmark harness — one section per paper table/figure.

  merging_effect      Fig. 3/6   perf loss vs #merges (+ rho refit)
  merging_efficiency  Fig. 7     SR vs ORIG / LDA* / OGS
  scalability         Fig. 8     SR vs corpus size
  coverage            Fig. 9     SR vs coverage ratio
  plan_search         Fig. 10-12 NAI/GRA/PSOA/PSOA++ times, alpha sweep
  batch_opt           Fig. 13/14 Alg. 4 cost & benefit
  session             (ours)     unified submit/submit_many API latency
                                 + device-backend cache hit rates
  serve               (ours)     multi-tenant service: coalesced vs
                                 serial throughput/p50/p95 under
                                 concurrent traffic + cross-session
                                 cache reuse
  gibbs_gap           (ours)     host exact CGS scan vs doc-blocked
                                 device sweep (latency + quality delta)
  merge_shard         (ours)     vocab-sharded ragged merge vs single
                                 device (launches, pad rows, per-device
                                 bytes, wall) over 8 forced host devices
  ingest              (ours)     streaming ingestion: freshness lag,
                                 speculative pre-training A/B (p50 +
                                 hit rate), compaction budget/quality
  chaos               (ours)     serve trace under injected faults:
                                 goodput, retry counts, breaker opens/
                                 reroutes, device-loss recovery time
  obs                 (ours)     tracing/metrics overhead (asserted
                                 < 5%) + Chrome trace artifact and
                                 span/metric cardinality
  kernels             (ours)     Pallas kernel parity timings
  roofline            (ours)     table from dry-run artifacts, if present

All sections drive MLego through the ``repro.api`` session surface
(QuerySpec -> MLegoSession.submit); none construct the deprecated
``QueryEngine`` directly.

``--quick`` shrinks every section so the whole harness finishes in
under ~2 min on CPU (the CI smoke job runs this).  ``--json PATH``
additionally dumps every section's rows as one JSON document — CI
uploads these as ``BENCH_*.json`` artifacts so the perf trajectory
accumulates across commits.

Usage: PYTHONPATH=src python -m benchmarks.run
           [--quick] [--only SECTION[,SECTION...]] [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import time


def _section(name):
    print(f"\n### {name}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated section names (default: all)")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write section rows as JSON to PATH")
    args = ap.parse_args()

    only = None if args.only is None else {
        s.strip() for s in args.only.split(",") if s.strip()}
    out = {}

    def want(name):
        return only is None or name in only

    t_start = time.perf_counter()

    if want("merging_effect"):
        _section("merging_effect (Fig. 3/6)")
        from benchmarks import merging_effect
        rows, ploss = merging_effect.run(
            n_docs=600 if args.quick else 1200,
            parts=(1, 2, 4, 8) if args.quick else (1, 2, 4, 8, 16))
        print("n_parts,lpp_scratch,lpp_mvb,lpp_mgs,dp_mvb,dp_mgs")
        for r in rows:
            print(",".join(f"{v:.4f}" if isinstance(v, float) else str(v)
                           for v in r))
        print(f"# fitted PerformanceLoss rho = {ploss.rho:.5f}")
        out["merging_effect"] = {"rows": [list(r) for r in rows],
                                 "rho": ploss.rho}

    if want("merging_efficiency"):
        _section("merging_efficiency (Fig. 7)")
        from benchmarks import merging_efficiency
        rows, t_mat = merging_efficiency.run(
            n_docs=600 if args.quick else 1500)
        print("method,time_s,lpp,SR")
        for name, t, lpp, sr in rows:
            print(f"{name},{t:.4f},{lpp:.4f},{sr:.2f}")
        print(f"# materialization {t_mat:.2f}s (offline)")
        out["merging_efficiency"] = {"rows": [list(r) for r in rows],
                                     "t_materialize_s": t_mat}

    if want("scalability"):
        _section("scalability (Fig. 8)")
        from benchmarks import merging_efficiency
        print("n_docs,method,time_s,SR")
        scal = []
        for n in ((400, 1000) if args.quick else (500, 1500, 4000)):
            rows, _ = merging_efficiency.run(n_docs=n)
            for name, t, _, sr in rows:
                print(f"{n},{name},{t:.4f},{sr:.2f}")
                scal.append([n, name, t, sr])
        out["scalability"] = {"rows": scal}

    if want("coverage"):
        _section("coverage (Fig. 9)")
        from benchmarks import coverage
        print("coverage,t_orig_s,t_mlego_s,SR,t_search_s,lpp")
        rows = list(coverage.run(n_docs=600 if args.quick else 1500))
        for r in rows:
            print(",".join(f"{v:.4f}" for v in r))
        out["coverage"] = {"rows": [list(r) for r in rows]}

    if want("plan_search"):
        _section("plan_search (Fig. 10/11/12)")
        from benchmarks import plan_search
        print("n_models,alpha,nai_s,nai_scored,gra_s,gra_scored,"
              "psoa_s,psoa_scored,psoa++_s,psoa++_scored")
        sizes = (6, 10, 14) if args.quick else (6, 10, 14, 18, 22)
        size_rows = list(plan_search.run_sizes(sizes=sizes))
        for r in size_rows:
            print(",".join(f"{x:.6f}" if isinstance(x, float) else str(x)
                           for x in r))
        print("alpha,psoa_s,n_scored,n_layers,method")
        alpha_rows = list(plan_search.run_alpha())
        for r in alpha_rows:
            print(",".join(f"{x:.6f}" if isinstance(x, float) else str(x)
                           for x in r))
        out["plan_search"] = {"sizes": [list(r) for r in size_rows],
                              "alpha": [list(r) for r in alpha_rows]}

    if want("batch_opt"):
        _section("batch_opt (Fig. 13/14)")
        from benchmarks import batch_opt_bench
        print("batch,models,search_s,n_scored,benefit,total_time,"
              "naive_time,oracle_time")
        bs = (2, 3) if args.quick else (2, 3, 4, 6)
        mp = (8, 16) if args.quick else (8, 16, 24)
        rows = list(batch_opt_bench.run(batch_sizes=bs, models_per=mp))
        for r in rows:
            print(",".join(f"{x:.6f}" if isinstance(x, float) else str(x)
                           for x in r))
        out["batch_opt"] = {"rows": [list(r) for r in rows]}

    if want("session"):
        _section("session (unified API latency)")
        from benchmarks import session_bench
        n_docs = 600 if args.quick else 1200
        rows, batch_row = session_bench.run(n_docs=n_docs, quick=args.quick)
        print("label,search_s,train_s,merge_s,n_reused,n_trained_tokens,"
              "plan_cached")
        for label, s, t, m, nr, nt, pc in rows:
            print(f"{label},{s:.4f},{t:.4f},{m:.4f},{nr},{nt},{pc}")
        print("# batch: shared_search_s,shared_train_s,merge_s,benefit,n")
        print("batch," + ",".join(
            f"{v:.4f}" if isinstance(v, float) else str(v)
            for v in batch_row))
        dev_rows, hit_rate = session_bench.run_device_cache(
            n_docs=n_docs, quick=args.quick)
        print("label,cache_hits,cache_misses,merge_device_ms,merge_s,"
              "plan_cached")
        for label, h, mi, dms, ms, pc in dev_rows:
            print(f"{label},{h},{mi},{dms:.3f},{ms:.4f},{pc}")
        print(f"# device cache hit-rate {hit_rate:.3f}")
        prov_rows = session_bench.run_providers(
            n_docs=n_docs, quick=args.quick)
        print("provider,mean_submit_s,total_s,plan_cache_hits,"
              "device_hit_rate")
        for provider, mean_s, total, hits, rate in prov_rows:
            print(f"{provider},{mean_s:.4f},{total:.4f},{hits},{rate:.3f}")
        pad = session_bench.run_padding(n_docs=n_docs, quick=args.quick)
        print(f"# padding: ragged {pad['pad_rows_ragged']} rows vs "
              f"bucketed {pad['pad_rows_bucketed']} vs widest "
              f"{pad['pad_rows_widest']} (parts {pad['part_counts']})")
        out["session"] = {"rows": [list(r) for r in rows],
                          "batch": list(batch_row),
                          "device_cache": [list(r) for r in dev_rows],
                          "device_cache_hit_rate": hit_rate,
                          "providers": [list(r) for r in prov_rows],
                          "padding": pad}

    if want("serve"):
        _section("serve (coalesced service vs serial session)")
        from benchmarks import serve_bench
        sv = serve_bench.run(n_docs=600 if args.quick else 1200,
                             quick=args.quick)
        s, c = sv["serial"], sv["coalesced"]
        print("mode,queries,wall_s,qps,p50_s,p95_s")
        for label, m in (("serial", s), ("coalesced", c)):
            print(f"{label},{m['queries']},{m['wall_s']:.3f},"
                  f"{m['qps']:.2f},{m['p50_s']:.4f},{m['p95_s']:.4f}")
        print(f"# speedup {sv['speedup']:.2f}x, mean coalesce width "
              f"{sv['mean_coalesce_width']:.2f} (max "
              f"{sv['max_coalesce_width']}), coalesce rate "
              f"{sv['coalesce_rate']:.2f}")
        cross = serve_bench.run_cross_session(
            n_docs=600 if args.quick else 1200, quick=args.quick)
        print(f"# cross-session: plan_cached={cross['second_plan_cached']} "
              f"device hits={cross['second_cache_hits']} "
              f"misses={cross['second_cache_misses']}")
        ol = serve_bench.run_open_loop(
            n_docs=600 if args.quick else 1200, quick=args.quick)
        print(f"# open-loop ({ol['n_tenants']} tenants, {ol['arrivals']} "
              f"arrivals, {ol['overload']:.1f}x overload): "
              f"p50 {ol['p50_ms']:.1f}ms p95 {ol['p95_ms']:.1f}ms "
              f"p99 {ol['p99_ms']:.1f}ms, shed_rate {ol['shed_rate']:.3f}, "
              f"degraded_frac {ol['degraded_frac']:.3f}, "
              f"p95_within_slo={ol['p95_within_slo']} "
              f"(slo {ol['slo_ms']:.1f}ms)")
        pc = serve_bench.run_pool_comparison(
            n_docs=600 if args.quick else 1200, quick=args.quick)
        print(f"# worker pools: single-loop "
              f"{pc['single_loop']['wall_s']:.2f}s vs pooled "
              f"{pc['pooled']['wall_s']:.2f}s "
              f"({pc['pool_speedup']:.2f}x)")
        out["serve"] = {**sv, "cross_session": cross,
                        "open_loop": ol, "pools": pc,
                        # hardening headline numbers, hoisted for the
                        # artifact trajectory
                        "p50_ms": ol["p50_ms"], "p95_ms": ol["p95_ms"],
                        "p99_ms": ol["p99_ms"],
                        "shed_rate": ol["shed_rate"],
                        "degraded_frac": ol["degraded_frac"]}

    if want("gibbs_gap"):
        _section("gibbs_gap (host exact scan vs blocked device sweep)")
        from benchmarks import gibbs_gap
        print("block_docs,n_blocks,host_scan_s,blocked_s,speedup,"
              "lpp_host,lpp_blocked,lpp_delta,top_word_overlap")
        gg_rows = gibbs_gap.rows(quick=args.quick)
        for r in gg_rows:
            print(f"{r['block_docs']},{r['n_blocks']},"
                  f"{r['host_scan_s']:.4f},{r['blocked_s']:.4f},"
                  f"{r['speedup']:.2f},{r['lpp_host']:.4f},"
                  f"{r['lpp_blocked']:.4f},{r['lpp_delta']:.4f},"
                  f"{r['top_word_overlap']:.3f}")
        out["gibbs_gap"] = {"rows": gg_rows}

    if want("merge_shard"):
        _section("merge_shard (vocab-sharded ragged merge, 8 devices)")
        from benchmarks import merge_shard_bench
        msd = merge_shard_bench.run(quick=args.quick)
        print("mode,shards,launches,pad_rows,per_device_bytes,wall_s")
        for label in ("single", "sharded"):
            m = msd[label]
            print(f"{label},{m['shards']},{m['launches']},{m['pad_rows']},"
                  f"{m['per_device_bytes']},{m['wall_s']:.4f}")
        print(f"# batch {msd['counts']} ({msd['rows']} rows, K={msd['k']}, "
              f"V={msd['v']}), sharded-vs-single max|err| "
              f"{msd['max_abs_err']:.2e}")
        out["merge_shard"] = msd

    if want("ingest"):
        _section("ingest (streaming freshness / speculation / compaction)")
        from benchmarks import ingest_bench
        ib = ingest_bench.run(n_docs=400 if args.quick else 800,
                              quick=args.quick)
        fr = ib["freshness"]
        print("batch,slice_lo,slice_hi,ingest_to_built_s,query_s,fresh,"
              "n_reused")
        for r in fr["rows"]:
            print(f"{r['batch']},{r['slice_lo']:.1f},{r['slice_hi']:.1f},"
                  f"{r['ingest_to_built_s']:.4f},{r['query_s']:.4f},"
                  f"{r['fresh']},{r['n_reused']}")
        print(f"# fresh-answered {fr['fresh_answered']}/{fr['queries']}, "
              f"builder lag mean {fr['freshness_lag_s_mean']:.3f}s "
              f"max {fr['freshness_lag_s_max']:.3f}s")
        sp = ib["speculation"]
        print("speculation,steady_p50_s,p95_s,hit_rate,segments")
        for label in ("off", "on"):
            m = sp[label]
            print(f"{label},{m['steady_p50_s']:.4f},{m['p95_s']:.4f},"
                  f"{m['hit_rate']:.2f},{m['speculated_segments']}")
        print(f"# steady-state hot-sigma speedup "
              f"{sp['steady_speedup']:.2f}x")
        cp = ib["compaction"]
        print(f"# compaction: {cp['bytes_before']} -> {cp['bytes_after']} "
              f"bytes (budget {cp['budget_bytes']}, under="
              f"{cp['under_budget']}), parts {cp['parts_before']} -> "
              f"{cp['parts_after']}, beta max|delta| "
              f"{cp['beta_max_abs_delta']:.2e}, topic overlap "
              f"{cp['topic_overlap']:.3f}")
        out["ingest"] = ib

    if want("chaos"):
        _section("chaos (serve goodput under injected faults)")
        from benchmarks import serve_bench
        cz = serve_bench.run_chaos(n_docs=600 if args.quick else 1200,
                                   quick=args.quick)
        rec = (f"{cz['recovery_s']:.3f}s" if cz["recovery_s"] is not None
               else "n/a")
        print(f"# chaos ({cz['fault_rate']:.0%} transient): goodput "
              f"{cz['goodput']:.3f} ({cz['answered']}/{cz['queries']}), "
              f"{cz['injected_failures']} faults, {cz['retries']} "
              f"retries, {cz['fallback_answers']} fallback answers")
        print(f"# breaker: opens {cz['breaker_opens']} (final "
              f"{cz['breaker_final_state']}), reroutes "
              f"{cz['breaker_reroutes']}, device-loss recovery {rec}, "
              f"workers_alive {cz['workers_alive']}")
        out["chaos"] = cz

    if want("obs"):
        _section("obs (tracing/metrics overhead)")
        from benchmarks import serve_bench
        ob = serve_bench.run_obs(n_docs=600 if args.quick else 1200,
                                 quick=args.quick,
                                 trace_path="BENCH_obs_trace.json")
        print(f"# overhead: untraced {ob['untraced_wall_s']:.3f}s vs "
              f"traced {ob['traced_wall_s']:.3f}s "
              f"({ob['overhead_frac']:+.2%}, budget <5%)")
        print(f"# spans: {ob['span_count']} across {ob['span_kinds']} "
              f"kinds; metrics: {ob['metric_lines']} exposition lines; "
              f"trace -> {ob['trace_path']}")
        out["obs"] = ob

    if want("kernels"):
        _section("kernels (interpret-mode parity timings)")
        from benchmarks import kernel_bench
        kernel_bench.run(quick=args.quick)

    if want("roofline"):
        _section("roofline (from dry-run artifacts)")
        import os
        from benchmarks import roofline
        if os.path.isdir("experiments/dryrun") and \
                os.listdir("experiments/dryrun"):
            rows = roofline.load("experiments/dryrun")
            print(roofline.render(rows, md=False))
        else:
            print("# no artifacts; run: PYTHONPATH=src python -m "
                  "repro.launch.dryrun")

    elapsed = time.perf_counter() - t_start
    print(f"\n# total bench time {elapsed:.1f}s")

    if args.json:
        doc = {"quick": args.quick, "sections": out, "elapsed_s": elapsed}
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"# wrote {args.json}")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
