"""Serving launcher: continuous-batching (repro.serve) vs static fixed-batch
decode, under a Poisson arrival process with heterogeneous prompt/generation
lengths.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --requests 16 --engine both --rate 50 --gen-max 32

The continuous engine defaults to CHUNKED prefill through the unified
ragged step (two jit compiles total); ``--bucketed`` restores the legacy
bucketed prefill → insert → decode trio for A/B comparisons, and
``--chunk-size`` / ``--chunk-rows`` set the per-tick prefill token budget.

``--paged`` swaps the dense slot cache for the block-table paged KV cache
(``--page-size`` rows per page, ``--pages`` physical pool pages; 0 sizes the
pool at dense-equivalent capacity), so cache HBM scales with actual request
lengths and admission is page-budgeted — see serve/README.md for the layout
and memory accounting.

``--replicas R`` switches to the Byzantine-tolerant replicated engine
(``repro.serve.replicated``): R decode replicas vote every token through the
``--vote`` rule with staleness-derived weights (``--lags``), while
``--byz-replicas`` + ``--attack`` inject corrupted logits and
``--dead`` / ``--hang`` model availability faults; per-replica health and
quarantine events are logged after the run.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --replicas 3 --byz-replicas 2 --attack sign_flip --requests 8

Timings are reported split into compile (jit warmup), prefill and decode —
the old single tokens/s figure folded all three together (including compile
time) and is kept as ``combined_tok_s`` for back-compat.
"""
from __future__ import annotations

import argparse
import copy

import jax

from repro.configs import ARCH_NAMES, get_config, smoke_config
from repro.core.attacks import LOGIT_ATTACKS, LogitAttackConfig
from repro.models.lm import init_lm
from repro.serve import (ReplicatedConfig, ReplicatedServeEngine, ServeConfig,
                         ServeEngine, synth_workload)
from repro.utils import enable_compile_cache, logger


def _csv_ints(text: str):
    return tuple(int(x) for x in text.split(",")) if text else ()


def _log_report(rep) -> None:
    mode = (f"chunked({rep.chunk_size})" if rep.chunked else "bucketed")
    logger.info(
        "[%s/%s] %d reqs | compile %.2fs | prefill %.3fs (%.0f tok/s) | "
        "decode %.3fs (%.0f tok/s, occupancy %.2f) | combined %.1f tok/s | "
        "ttft p50 %.3fs p99 %.3fs | latency p50 %.3fs p99 %.3fs",
        rep.engine, mode, rep.n_requests, rep.compile_s, rep.prefill_s,
        rep.prefill_tok_s, rep.decode_s, rep.decode_tok_s,
        rep.mean_occupancy, rep.combined_tok_s, rep.ttft_p50_s,
        rep.ttft_p99_s, rep.latency_p50_s, rep.latency_p99_s)
    if rep.paged:
        logger.info(
        "[%s] paged cache: %d pages x %d rows | page occupancy %.2f | "
        "%.1f pages/request",
        rep.engine, rep.n_pages, rep.page_size, rep.mean_page_occupancy,
        rep.mean_pages_per_req)


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b", choices=list(ARCH_NAMES))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", default="continuous",
                    choices=["continuous", "static", "both"])
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--prefill-batch", type=int, default=4)
    ap.add_argument("--prompt-min", type=int, default=8)
    ap.add_argument("--prompt-max", type=int, default=32)
    ap.add_argument("--gen-min", type=int, default=4)
    ap.add_argument("--gen-max", type=int, default=16)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate (req/s); 0 = all at t=0")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--bucketed", action="store_true",
                    help="legacy bucketed-prefill trio instead of the "
                         "default chunked unified step")
    ap.add_argument("--chunk-size", type=int, default=0,
                    help="prefill chunk width (tokens); 0 = page size if "
                         "--paged else 16")
    ap.add_argument("--chunk-rows", type=int, default=1,
                    help="max prefill chunk rows per mixed tick")
    ap.add_argument("--paged", action="store_true",
                    help="block-table paged KV cache (serve/cache.py)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV rows per page (with --paged)")
    ap.add_argument("--pages", type=int, default=0,
                    help="physical pool pages; 0 = dense-equivalent capacity")
    # Byzantine-tolerant replicated serving (repro.serve.replicated)
    ap.add_argument("--replicas", type=int, default=0,
                    help="decode replicas voting each token; 0 = single engine")
    ap.add_argument("--byz-replicas", default="",
                    help="comma-separated Byzantine replica ids (e.g. 2 or 1,2)")
    ap.add_argument("--attack", default="none", choices=list(LOGIT_ATTACKS),
                    help="logit attack the Byzantine replicas transmit")
    ap.add_argument("--lags", default="",
                    help="comma-separated per-replica checkpoint staleness "
                         "(versions behind); empty = all fresh")
    ap.add_argument("--vote", default="cwmed",
                    help="repro.agg spec for the per-token logit vote")
    ap.add_argument("--dead", default="",
                    help="comma-separated replica ids that stop responding")
    ap.add_argument("--hang", default="",
                    help="comma-separated replica ids that intermittently stall")
    ap.add_argument("--obs-dir", default="",
                    help="write repro.obs telemetry here: "
                         "<dir>/serve.metrics.jsonl + <dir>/serve.trace.json "
                         "(Perfetto-loadable; summarize with "
                         "python -m repro.launch.obs)")
    ap.add_argument("--no-device-metrics", action="store_true",
                    help="with --obs-dir: host-side spans/rows only, keep "
                         "the jitted steps' uninstrumented HLO")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if not cfg.supports_decode():
        raise SystemExit(f"{cfg.name} is encoder-only: no decode path")
    extra = cfg.n_patches if cfg.frontend == "vision" else 0
    max_len = extra + args.prompt_max + args.gen_max
    params = init_lm(jax.random.PRNGKey(args.seed), cfg)

    workload = synth_workload(
        args.requests, cfg.vocab, seed=args.seed,
        prompt_lens=(args.prompt_min, args.prompt_max),
        gen_lens=(args.gen_min, args.gen_max), rate=args.rate,
        n_patches=extra, d_model=cfg.d_model if extra else 0)

    scfg = ServeConfig(
        n_slots=args.slots, max_len=max_len,
        max_prefill_batch=args.prefill_batch,
        temperature=args.temperature, top_k=args.top_k,
        eos_id=args.eos_id, seed=args.seed,
        chunked=not args.bucketed, chunk_size=args.chunk_size,
        chunk_rows=args.chunk_rows,
        paged=args.paged, page_size=args.page_size, n_pages=args.pages)

    engines = (["continuous", "static"] if args.engine == "both"
               else [args.engine])
    rcfg = None
    if args.replicas > 0:
        rcfg = ReplicatedConfig(
            n_replicas=args.replicas, vote=args.vote,
            attack=LogitAttackConfig(name=args.attack),
            byz=_csv_ints(args.byz_replicas), lags=_csv_ints(args.lags),
            dead=_csv_ints(args.dead), hang=_csv_ints(args.hang),
            attack_seed=args.seed)
    obs = None
    if args.obs_dir:
        from repro.obs import RunObs
        obs = RunObs.open(args.obs_dir, "serve",
                          device_metrics=not args.no_device_metrics)
    reports = {}
    for name in engines:
        reqs = [copy.deepcopy(r) for r in workload]
        if rcfg is not None:
            rep = ReplicatedServeEngine(cfg, params, scfg, rcfg,
                                        engine=name, obs=obs).run(reqs)
        else:
            rep = ServeEngine(cfg, params, scfg, engine=name,
                              obs=obs).run(reqs)
        _log_report(rep)
        if rcfg is not None:
            for h in rep.replicas:
                logger.info(
                    "[%s] replica %d (%s, lag %.0f, mass %.2f): voted %d | "
                    "missed %d | divergent %d | evictions %d | score %.3f",
                    name, h["replica"], h["role"], h["lag"], h["weight"],
                    h["tokens_voted"], h["tokens_missed"],
                    h["divergent_tokens"], h["evictions"], h["mean_score"])
            if rep.quarantine_events:
                logger.info("[%s] quarantine events: %s (first at decode "
                            "step %s)", name, rep.quarantine_events,
                            rep.first_quarantine_step)
        reports[name] = rep
    if len(reports) == 2:
        c, s = reports["continuous"], reports["static"]
        if s.decode_tok_s > 0:
            logger.info("continuous/static decode speedup: %.2fx",
                        c.decode_tok_s / s.decode_tok_s)

    if obs is not None:
        obs.close()
        logger.info("obs: wrote %s/serve.metrics.jsonl + serve.trace.json",
                    args.obs_dir)

    rep = reports[engines[0]]
    return {"reports": {k: v.as_dict() for k, v in reports.items()},
            "outputs": rep.outputs, "tok_per_s": rep.combined_tok_s}


if __name__ == "__main__":
    main()
