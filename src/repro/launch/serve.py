"""Serving launcher: RoBatch's data plane as a CLI, in two modes.

``engine`` — bring up one continuous-batching engine for an architecture at
its published widths (``--reduced`` serves its smoke config instead), with
random weights from ``--seed``, and serve a batched-prompt workload (the
original single-model path; ``chip_smoke.py`` drives it on the chip)::

    PYTHONPATH=src python -m repro.launch.serve engine --arch tiny-m --requests 12
    python -m repro.launch.serve engine --arch qwen1.5-0.5b --batch-prompt 4

``online`` — the full online serving layer: build the pool a spec describes,
fit the modeling stage once, then stream a Poisson arrival workload through
the pluggable policy under a rolling budget, the response cache and the
circuit breakers::

    PYTHONPATH=src python -m repro.launch.serve online --task agnews --qps 40 \
        --duration 20 --window 0.25 --budget-x 3.0
    PYTHONPATH=src python -m repro.launch.serve online --policy routellm
    PYTHONPATH=src python -m repro.launch.serve online --spec run.json

``--realtime`` paces the stream against the wall clock: a live Poisson
arrival thread (``LiveArrivalSource``) submits the same seeded stream at its
due times while the server fires one scheduling round per window boundary —
the run takes ~``--duration`` wall seconds and reports window-pacing lateness.
``--replicas N`` builds every pool member as an N-engine ``ReplicaSet``
(least-loaded dispatch, per-window capacity caps in the scheduler).
``--autoscale`` sizes the pool at serving time: a backlog-driven control
loop (``repro.serving.autoscale``) grows each ReplicaSet under capacity
pressure and drains it back when idle, between ``--min-replicas`` and
``--max-replicas``::

    PYTHONPATH=src python -m repro.launch.serve online --qps 40 \
        --autoscale --min-replicas 1 --max-replicas 4

``--semantic-cache`` adds the embedding-space near-duplicate response cache
(``repro.serving.semcache``) behind the exact-match one; ``--sim-threshold``
sets its cosine hit threshold (docs/caching.md)::

    PYTHONPATH=src python -m repro.launch.serve online --qps 40 \
        --semantic-cache --sim-threshold 0.9

``http`` — the OpenAI-compatible HTTP front-end (``repro.http``): fit the
same control plane, then serve it over the wire — ``POST
/v1/chat/completions`` (SSE streaming with ``"stream": true``), ``GET
/v1/models``, ``GET /healthz`` and Prometheus ``GET /metrics`` — until
SIGINT/SIGTERM (or ``--max-seconds``)::

    PYTHONPATH=src python -m repro.launch.serve http --port 8000
    PYTHONPATH=src python -m repro.launch.serve http --port 0 --policy robatch \
        --replicas 2 --autoscale --max-replicas 4
    curl -N localhost:8000/v1/chat/completions -d \
        '{"messages":[{"role":"user","content":"#7"}],"stream":true}'

``--port 0`` binds an ephemeral port (printed on the ``listening on`` line —
how ``tools/smoke.sh`` runs it).

``--policy`` selects any name from the policy registry
(``repro.api.list_policies()``); ``--spec`` takes a ``RunSpec`` JSON (a file
path or an inline JSON string) and subsumes the individual flags.  Legacy
flag-only invocations (no subcommand) default to ``engine`` mode, and the
pre-spec flags (``--task``/``--family``/``--n-train``/``--coreset``/
``--seed``) keep working as a deprecation shim that overrides the spec.
"""
import argparse
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def enable_compile_cache() -> None:
    """Keep JAX's persistent compilation cache at a fixed path, so a second
    process on the same checkout reuses what the first compiled.  A
    ``JAX_COMPILATION_CACHE_DIR`` from the environment wins (JAX reads it
    itself); otherwise the cache lives in ``<repo>/.jax_cache``."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(REPO_ROOT, ".jax_cache"))


def engine_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="serve engine")
    ap.add_argument("--arch", default="tiny-s")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch-prompt", type=int, default=0,
                    help="pack N queries per request (batch prompting); 0 = single")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--decode-block", type=int, default=8,
                    help="tokens generated per fused on-device decode dispatch")
    ap.add_argument("--contiguous", action="store_true",
                    help="use the contiguous (max_slots, max_len) KV layout "
                         "instead of the default paged block pool")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page in the paged layout")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the reduced (smoke) config of the arch "
                         "instead of its published widths")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the prompts")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy, the default)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep the k highest-probability tokens (0 = all)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = all)")
    ap.add_argument("--gen-seed", type=int, default=0,
                    help="PRNG seed for sampled decoding (same seed ⇒ "
                         "bit-identical streams at any decode block/slot count)")
    ap.add_argument("--draft-member", default="",
                    help="arch whose model drafts for --arch via speculative "
                         "decoding (e.g. tiny-s drafting for tiny-m)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculation depth with --draft-member")
    return ap


def engine_config(args, device_bytes=None):
    """The architecture ``serve engine`` builds: the registered one at its
    published widths, or its smoke config with ``--reduced``.  What the
    engine cannot serve as asked fails here with a message, never with a
    silent cut.  ``device_bytes`` is the device memory the weights must fit
    in (``None`` when the backend does not report it)."""
    from repro.config import get_arch

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.vocab_size < 259 or cfg.enc_dec or cfg.frontend:
        raise SystemExit(f"{cfg.name}: byte-tokenizer text serving needs a plain "
                         f"decoder with vocab ≥ 259")
    weight_bytes = cfg.param_count() * (2 if cfg.dtype == "bfloat16" else 4)
    if device_bytes and weight_bytes > device_bytes:
        raise SystemExit(f"{cfg.name}: {weight_bytes / 2**30:.1f} GiB of weights "
                         f"do not fit the device's {device_bytes / 2**30:.1f} GiB; "
                         f"pass --reduced to serve its smoke config")
    return cfg


def build_engine(args):
    """Everything ``serve engine`` serves, from its parsed flags: the model
    with random weights from ``--seed``, its engine, and ``--requests``
    requests (batch prompts of ``--batch-prompt`` queries under one system
    prompt when that is set).  Returns ``(cfg, engine, requests)``."""
    import jax
    import numpy as np

    from repro.config import ShardingConfig, get_arch
    from repro.models.transformer import Model
    from repro.serving.batcher import BatchPromptFormatter
    from repro.serving.engine import Request, ServingEngine
    from repro.serving.generation import GenerationConfig

    mem = jax.devices()[0].memory_stats() or {}
    cfg = engine_config(args, mem.get("bytes_limit"))
    model = Model(cfg, ShardingConfig(remat="none"))
    params = model.init(jax.random.PRNGKey(args.seed))
    paged = not args.contiguous
    if paged and (cfg.enc_dec or any(k != "attn" for k in cfg.layer_kinds())):
        print(f"{cfg.name}: paged KV needs a decoder-only global-attention "
              f"stack; falling back to the contiguous layout")
        paged = False
    if args.draft_member:
        from repro.serving.speculative import SpeculativeEngine

        dcfg = get_arch(args.draft_member)
        if not paged:
            raise SystemExit("--draft-member needs the paged KV layout "
                             "(drop --contiguous)")
        dmodel = Model(dcfg, ShardingConfig(remat="none"))
        dparams = dmodel.init(jax.random.PRNGKey(args.seed))
        engine = SpeculativeEngine(model, params, dmodel, dparams,
                                   max_slots=args.slots, max_len=args.max_len,
                                   spec_k=args.spec_k,
                                   page_size=args.page_size)
    else:
        engine = ServingEngine(model, params, max_slots=args.slots,
                               max_len=args.max_len,
                               decode_block=args.decode_block,
                               paged=paged, page_size=args.page_size)
    fmt = BatchPromptFormatter("Answer each question.")

    gen = None
    if args.temperature > 0 or args.top_k > 0 or args.top_p < 1.0:
        gen = GenerationConfig(max_new=args.max_new,
                               temperature=args.temperature,
                               top_k=args.top_k, top_p=args.top_p,
                               seed=args.gen_seed)
    rng = np.random.default_rng(args.seed)
    prompts = []
    for i in range(args.requests):
        qs = [f"{rng.integers(0, 99)}+{rng.integers(0, 99)}"
              for _ in range(max(args.batch_prompt, 1))]
        prompts.append(fmt.format(qs) if args.batch_prompt else fmt.tokenizer.encode(qs[0]))
    reqs = [Request(rid=i, tokens=p, max_new=args.max_new, gen=gen)
            for i, p in enumerate(prompts)]
    return cfg, engine, reqs


def engine_main(argv):
    cfg, engine, reqs = build_engine(engine_parser().parse_args(argv))
    t0 = time.time()
    engine.serve(reqs)
    dt = time.time() - t0
    done = sum(r.done for r in reqs)
    out_toks = sum(len(r.out_tokens) for r in reqs)
    print(f"{cfg.name}: served {done}/{len(reqs)} requests "
          f"({out_toks} tokens) in {dt:.1f}s via {engine.max_slots} slots")
    occ = engine.kv_occupancy()
    if occ.get("paged"):
        print(f"  kv pages: {occ['pages_used']}/{occ['n_pages']} live "
              f"(peak {occ['peak_pages']}), {occ['prefix_shares']} prefix "
              f"shares, {occ['cow_forks']} CoW forks")
    if hasattr(engine, "accept_rate"):
        print(f"  speculative: k={engine.spec_k} rounds={engine.n_rounds} "
              f"accept={engine.accept_rate():.2f} bonus={engine.n_bonus}")
    for r in reqs[:3]:
        print(f"  req{r.rid}: prompt {len(r.tokens)} toks -> "
              f"{engine.tok.decode(r.out_tokens)[:48]!r}")


def _add_robust_flags(ap):
    """Shared online/http uncertainty-robust scheduling flags (they land on
    the PolicySpec params, so --spec files can declare the same fields)."""
    ap.add_argument("--robust-lambda", type=float, default=None,
                    help="uncertainty penalty λ of the robust frontier walk "
                         "(utility − λ·σ); 0 = the point-estimate walk "
                         "(docs/robustness.md)")
    ap.add_argument("--cost-margin", type=float, default=None,
                    help="worst-case budget margin: the walk draws the window "
                         "budget down at cost·(1+margin)")


def _apply_robust_flags(prog, spec, args):
    if args.robust_lambda is None and args.cost_margin is None:
        return
    params = dict(spec.policy.params)
    if args.robust_lambda is not None:
        params["robust"] = args.robust_lambda
    if args.cost_margin is not None:
        params["cost_margin"] = args.cost_margin
    spec.policy.params = params


def _add_generation_flags(ap):
    """Shared online/http sampling + speculative-decoding flags (they land on
    the PoolSpec, so --spec files can declare the same fields)."""
    ap.add_argument("--temperature", type=float, default=None,
                    help="default sampling temperature for real pool members "
                         "(0 = greedy)")
    ap.add_argument("--top-p", type=float, default=None,
                    help="nucleus sampling mass (1.0 = all)")
    ap.add_argument("--gen-seed", type=int, default=None,
                    help="PRNG seed for sampled decoding")
    ap.add_argument("--draft-member", default=None,
                    help="tiny pool: cheap member that drafts for the more "
                         "expensive ones (routed speculative decoding)")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="speculation depth with --draft-member (default 4)")


def _apply_generation_flags(prog, spec, args):
    if args.temperature is not None:
        spec.pool.temperature = args.temperature
    if args.top_p is not None:
        spec.pool.top_p = args.top_p
    if args.gen_seed is not None:
        spec.pool.gen_seed = args.gen_seed
    if args.draft_member is not None:
        if spec.pool.kind != "tiny":
            raise SystemExit(f"{prog}: --draft-member needs the tiny real "
                             f"pool (kind='tiny'), not {spec.pool.kind!r}")
        spec.pool.draft_member = args.draft_member
    if args.spec_k is not None:
        spec.pool.spec_k = args.spec_k


def _online_spec(args):
    """Resolve the RunSpec: --spec JSON (file or inline) as the base, legacy
    per-field flags as a deprecation shim layered on top."""
    from repro.api import PolicySpec, PoolSpec, RunSpec

    legacy = {k: v for k, v in [("task", args.task), ("family", args.family),
                                ("n_train", args.n_train), ("coreset", args.coreset),
                                ("seed", args.seed)] if v is not None}
    if args.spec:
        if args.spec.lstrip().startswith("{"):
            text = args.spec                 # inline JSON
        else:
            with open(args.spec) as f:       # else a file path: a typo should
                text = f.read()              # fail as file-not-found, not JSON
        spec = RunSpec.from_json(text)
        if legacy:
            print(f"serve online: legacy flags {sorted(legacy)} override the "
                  f"spec (deprecated; prefer editing --spec)")
            if "task" in legacy:
                spec.pool.task = legacy["task"]
            if "family" in legacy:
                spec.pool.family = legacy["family"]
            if "n_train" in legacy:
                spec.pool.n_train = legacy["n_train"]
            if "coreset" in legacy:
                spec.coreset_size = legacy["coreset"]
            if "seed" in legacy:
                spec.seed = spec.pool.seed = legacy["seed"]
    else:
        spec = RunSpec(
            pool=PoolSpec(task=legacy.get("task", "agnews"),
                          family=legacy.get("family", "qwen3"),
                          n_train=legacy.get("n_train", 512), n_val=128,
                          n_test=512, seed=legacy.get("seed", 0)),
            router="knn", coreset_size=legacy.get("coreset", 64),
            seed=legacy.get("seed", 0))
    if args.policy is not None:
        spec.policy = PolicySpec(args.policy)
    return spec


def online_main(argv):
    ap = argparse.ArgumentParser(prog="serve online")
    ap.add_argument("--policy", default=None,
                    help="registered policy name (repro.api.list_policies())")
    ap.add_argument("--spec", default=None,
                    help="RunSpec JSON — a file path or an inline JSON string")
    ap.add_argument("--task", default=None, help="workload benchmark name")
    ap.add_argument("--family", default=None, help="simulated pool family")
    ap.add_argument("--qps", type=float, default=40.0, help="offered load")
    ap.add_argument("--duration", type=float, default=20.0, help="stream length (s, virtual)")
    ap.add_argument("--window", type=float, default=0.25, help="admission window (s)")
    ap.add_argument("--budget-x", type=float, default=3.0,
                    help="budget rate = qps × cheapest-state cost × this factor")
    ap.add_argument("--repeat-frac", type=float, default=0.2,
                    help="fraction of arrivals re-asking an earlier query (cache hits)")
    ap.add_argument("--realtime", action="store_true",
                    help="pace against the wall clock behind a live arrival thread")
    ap.add_argument("--replicas", type=int, default=None,
                    help="engines per pool member (ReplicaSet when > 1)")
    ap.add_argument("--autoscale", action="store_true",
                    help="backlog-driven replica autoscaling (ReplicaSet."
                         "scale_to between --min-replicas and --max-replicas)")
    ap.add_argument("--min-replicas", type=int, default=None,
                    help="autoscale floor (default 1)")
    ap.add_argument("--max-replicas", type=int, default=None,
                    help="autoscale ceiling (default 4 with --autoscale)")
    ap.add_argument("--semantic-cache", action="store_true",
                    help="embedding-space near-duplicate response cache "
                         "(repro.serving.semcache; see docs/caching.md)")
    ap.add_argument("--sim-threshold", type=float, default=None,
                    help="semantic-cache cosine hit threshold (default 0.92)")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="wrap every pool member in a seeded ChaosMember fault "
                         "injector (latency noise everywhere, a short error "
                         "burst on the most expensive member) — the smoke "
                         "suite's degraded-path leg (docs/robustness.md)")
    _add_generation_flags(ap)
    _add_robust_flags(ap)
    ap.add_argument("--n-train", type=int, default=None)
    ap.add_argument("--coreset", type=int, default=None)
    ap.add_argument("--seed", type=int, default=None)
    args = ap.parse_args(argv)

    import numpy as np

    from repro.api import Gateway, UnknownPolicyError, get_policy, list_policies
    from repro.data import BENCHMARKS
    from repro.serving.online import OnlineConfig, poisson_arrivals

    if args.qps <= 0:
        raise SystemExit("serve online: --qps must be positive")
    spec = _online_spec(args)
    if args.replicas is not None:
        spec.pool.replicas = args.replicas
    if args.min_replicas is not None:
        spec.pool.min_replicas = args.min_replicas
    if args.max_replicas is not None:
        spec.pool.max_replicas = args.max_replicas
    if args.autoscale and spec.pool.max_replicas <= 0:
        spec.pool.max_replicas = 4               # sensible default ceiling
    if args.semantic_cache:
        spec.pool.semantic_cache = True
    if args.sim_threshold is not None:
        spec.pool.semantic_cache = True
        spec.pool.sim_threshold = args.sim_threshold
    _apply_generation_flags("serve online", spec, args)
    _apply_robust_flags("serve online", spec, args)
    if spec.pool.kind == "simulated" and spec.pool.task not in BENCHMARKS:
        raise SystemExit(f"serve online: unknown task {spec.pool.task!r}; "
                         f"known: {sorted(BENCHMARKS)}")
    try:
        get_policy(spec.policy.name)
    except UnknownPolicyError:
        raise SystemExit(f"serve online: unknown policy {spec.policy.name!r}; "
                         f"known: {list_policies()}")

    gw = Gateway.from_spec(spec)
    print(f"fitting RoBatch on {spec.pool.task}/{spec.pool.family} "
          f"({spec.pool.n_train} train, coreset {spec.coreset_size})...")
    gw.fit()
    rb = gw.robatch

    test = gw.wl.subset_indices("test")
    base = float(rb.cost_model.state_cost(0, rb.calibrations[0].b_effect, test).mean())
    rate = args.qps * base * args.budget_x
    autoscale = spec.pool.autoscale_policy() if args.autoscale else None
    cfg = OnlineConfig(budget_per_s=rate, window_s=args.window,
                       realtime=args.realtime, autoscale=autoscale)
    rng = np.random.default_rng(spec.seed)
    arrivals = poisson_arrivals(rng, args.qps, args.duration, test,
                                repeat_frac=args.repeat_frac)
    mode = "live wall-clock" if args.realtime else "virtual-clock"
    print(f"streaming {len(arrivals)} arrivals at {args.qps} qps ({mode}) "
          f"through policy={spec.policy.name}, window {args.window}s, "
          f"budget ${rate:.6f}/s...")
    chaos = None
    if args.chaos is not None:
        from repro.serving.fault import ChaosMember

        # latency noise everywhere; a short (sub-breaker-threshold) error
        # burst on the most expensive member so the degraded path exercises
        # reroutes while every breaker ends the run CLOSED
        last = len(gw.pool) - 1
        chaos = [ChaosMember(m, seed=args.chaos + k, latency_noise_s=0.002,
                             fail_from=1 if k == last else 10**9,
                             fail_until=3 if k == last else 10**9)
                 for k, m in enumerate(gw.pool)]
    t_wall = time.monotonic()
    stats = gw.serve(arrivals, cfg, live=args.realtime, pool=chaos)
    wall = time.monotonic() - t_wall
    srv = gw.server

    print(stats.summary())
    if args.realtime:
        late = [w.late_s for w in stats.windows]
        print(f"realtime: {wall:.2f}s wall for a {args.duration:.0f}s stream · "
              f"{len(late)} windows · max window lateness "
              f"{max(late, default=0.0) * 1e3:.1f}ms")
        if getattr(srv, "pacer_leaked", False):
            print("serve online: WARNING arrival pacer thread leaked past "
                  "shutdown join", file=sys.stderr)
    by_model = {}
    for r in srv.completed:
        if r.model is not None and not r.cache_hit:
            key = (srv.pool[r.model].name, r.batch)
            by_model[key] = by_model.get(key, 0) + 1
    print("dispatch mix (model, batch) -> queries:")
    for key in sorted(by_model, key=lambda t: (t[0], t[1] or 0)):
        print(f"  {key[0]:12s} b={key[1]}: {by_model[key]}")
    deferred = sum(w.n_deferred for w in stats.windows)
    print(f"policy={spec.policy.name} windows={len(stats.windows)} "
          f"deferred={deferred} shed={sum(w.n_shed for w in stats.windows)} "
          f"cache_entries={len(srv.cache)}")
    if srv.semcache is not None:
        sc = srv.semcache.stats()
        print(f"semcache: hits={sc['hits']} misses={sc['misses']} "
              f"entries={sc['entries']} bytes={sc['bytes']} "
              f"threshold={srv.semcache.cfg.sim_threshold} "
              f"utility_loss={sc['utility_loss']:.4f}")
    if chaos is not None:
        closed = all(br.state.value == "closed" for br in srv.breakers)
        print(f"chaos: seed={args.chaos} calls={sum(c.n_calls for c in chaos)} "
              f"faults={sum(c.n_faults for c in chaos)} "
              f"hangs={sum(c.n_hangs for c in chaos)} breakers_closed={closed}")
    if srv.autoscaler is not None:
        print(srv.autoscaler.summary())
        for e in srv.autoscaler.events:
            print(f"  t={e.t:7.2f}s {e.member}: {e.from_n} -> {e.to_n} ({e.reason})")


def http_main(argv):
    ap = argparse.ArgumentParser(prog="serve http")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000,
                    help="bind port (0 = ephemeral; the bound port is printed "
                         "on the 'listening on' line)")
    ap.add_argument("--policy", default=None,
                    help="registered policy name (repro.api.list_policies())")
    ap.add_argument("--spec", default=None,
                    help="RunSpec JSON — a file path or an inline JSON string")
    ap.add_argument("--task", default=None, help="workload benchmark name")
    ap.add_argument("--family", default=None, help="simulated pool family")
    ap.add_argument("--qps", type=float, default=40.0,
                    help="assumed offered load for budget sizing")
    ap.add_argument("--window", type=float, default=0.1, help="admission window (s)")
    ap.add_argument("--budget-x", type=float, default=3.0,
                    help="budget rate = qps × cheapest-state cost × this factor")
    ap.add_argument("--replicas", type=int, default=None,
                    help="engines per pool member (ReplicaSet when > 1)")
    ap.add_argument("--autoscale", action="store_true",
                    help="backlog-driven replica autoscaling between "
                         "--min-replicas and --max-replicas")
    ap.add_argument("--min-replicas", type=int, default=None,
                    help="autoscale floor (default 1)")
    ap.add_argument("--max-replicas", type=int, default=None,
                    help="autoscale ceiling (default 4 with --autoscale)")
    ap.add_argument("--semantic-cache", action="store_true",
                    help="embedding-space near-duplicate response cache "
                         "(repro.serving.semcache; see docs/caching.md)")
    ap.add_argument("--sim-threshold", type=float, default=None,
                    help="semantic-cache cosine hit threshold (default 0.92)")
    _add_generation_flags(ap)
    _add_robust_flags(ap)
    ap.add_argument("--max-seconds", type=float, default=0.0,
                    help="serve for N wall seconds then exit (0 = until "
                         "SIGINT/SIGTERM)")
    ap.add_argument("--n-train", type=int, default=None)
    ap.add_argument("--coreset", type=int, default=None)
    ap.add_argument("--seed", type=int, default=None)
    args = ap.parse_args(argv)

    import signal
    import threading

    from repro.api import Gateway, UnknownPolicyError, get_policy, list_policies
    from repro.data import BENCHMARKS
    from repro.serving.online import OnlineConfig

    if args.qps <= 0:
        raise SystemExit("serve http: --qps must be positive")
    spec = _online_spec(args)
    if args.replicas is not None:
        spec.pool.replicas = args.replicas
    if args.min_replicas is not None:
        spec.pool.min_replicas = args.min_replicas
    if args.max_replicas is not None:
        spec.pool.max_replicas = args.max_replicas
    if args.autoscale and spec.pool.max_replicas <= 0:
        spec.pool.max_replicas = 4
    if args.semantic_cache:
        spec.pool.semantic_cache = True
    if args.sim_threshold is not None:
        spec.pool.semantic_cache = True
        spec.pool.sim_threshold = args.sim_threshold
    _apply_generation_flags("serve http", spec, args)
    _apply_robust_flags("serve http", spec, args)
    if spec.pool.kind == "simulated" and spec.pool.task not in BENCHMARKS:
        raise SystemExit(f"serve http: unknown task {spec.pool.task!r}; "
                         f"known: {sorted(BENCHMARKS)}")
    try:
        get_policy(spec.policy.name)
    except UnknownPolicyError:
        raise SystemExit(f"serve http: unknown policy {spec.policy.name!r}; "
                         f"known: {list_policies()}")

    gw = Gateway.from_spec(spec)
    print(f"fitting RoBatch on {spec.pool.task}/{spec.pool.family} "
          f"({spec.pool.n_train} train, coreset {spec.coreset_size})...",
          flush=True)
    gw.fit()
    rb = gw.robatch

    test = gw.wl.subset_indices("test")
    base = float(rb.cost_model.state_cost(0, rb.calibrations[0].b_effect, test).mean())
    rate = args.qps * base * args.budget_x
    autoscale = spec.pool.autoscale_policy() if args.autoscale else None
    cfg = OnlineConfig(budget_per_s=rate, window_s=args.window,
                       realtime=True, autoscale=autoscale)
    fe = gw.serve_http(cfg, host=args.host, port=args.port)
    print(f"serve http: listening on http://{args.host}:{fe.port} "
          f"(policy={spec.policy.name}, {len(gw.pool)} members, "
          f"window {args.window}s, budget ${rate:.6f}/s)", flush=True)

    stop = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: stop.set())
    t0 = time.monotonic()
    while not stop.is_set():
        stop.wait(0.25)
        if args.max_seconds and time.monotonic() - t0 >= args.max_seconds:
            break
    fe.stop()
    srv = gw.server
    if fe.threads_leaked:
        print(f"serve http: shutdown LEAKED threads {fe.threads_leaked} — "
              f"{fe.n_http_requests} http requests, "
              f"{len(srv.completed)} completed", flush=True)
    else:
        print(f"serve http: shutdown clean — {fe.n_http_requests} http "
              f"requests, {len(srv.completed)} completed, "
              f"{len(srv.windows)} windows, "
              f"${srv.bucket.total_spent:.6f} spent", flush=True)
    if srv.semcache is not None:
        sc = srv.semcache.stats()
        print(f"semcache: hits={sc['hits']} misses={sc['misses']} "
              f"entries={sc['entries']} bytes={sc['bytes']}", flush=True)
    if srv.windows:
        print(f"  last window: {srv.windows[-1].summary()}", flush=True)


def main():
    enable_compile_cache()
    argv = sys.argv[1:]
    if argv and argv[0] in ("engine", "online", "http"):
        mode, rest = argv[0], argv[1:]
    else:
        mode, rest = "engine", argv     # legacy: bare flags mean engine mode
    {"online": online_main, "http": http_main}.get(mode, engine_main)(rest)


if __name__ == "__main__":
    main()
