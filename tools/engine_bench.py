"""Engine-level serving throughput: aggregate tok/s through the full
continuous-batching engine (scheduler, prefill, paged KV, sampling, stop
handling) — the number a user of the HTTP server actually sees, vs
bench.py's raw decode-step roofline.

    python tools/engine_bench.py [--config llama2-7b] [--requests 64]
        [--prompt-len 128] [--max-tokens 64] [--batch 24]

Gang mode (--gang 2) measures the multi-host lockstep control plane
(serve/multihost.py) against the single-process engine on the SAME mesh
shape: it spawns a jax.distributed gang of this script, runs the load on
the leader, then runs an identical single-process engine over the same
device count, and prints ONE JSON line with aggregate tok/s for both,
the TTFT delta, and per-iteration broadcast wall-time percentiles from
StepSync.timings. `--long-admission N` adds a prompt of N tokens whose
JSON-encoded admission broadcast overflows the 1 KB inline buffer — the
two-collective path an >=8k-token prompt always takes — and reports that
broadcast's size and wall time separately.

On CPU this is the measured stand-in for the pending hardware session
(docs/performance.md "Lockstep control-plane overhead"): the mechanism
cost — events serialized, N-byte collective, mirrored scheduler — is
real on any backend; only the ICI transfer time needs the chip.
"""
import argparse
import asyncio
import json
import os
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _percentiles_ms(samples) -> dict:
    """{count, p50, p90, p99, max} in milliseconds from raw seconds."""
    if not samples:
        return {"count": 0}
    xs = sorted(samples)

    def pick(q):
        return xs[min(len(xs) - 1, int(q * len(xs)))] * 1e3

    return {
        "count": len(xs),
        "p50": round(pick(0.50), 3),
        "p90": round(pick(0.90), 3),
        "p99": round(pick(0.99), 3),
        "max": round(xs[-1] * 1e3, 3),
    }


def build_prompts(a, cfg):
    import numpy as np

    rng = np.random.default_rng(0)
    if a.repetitive:
        # Repeated n-grams: the prompt-lookup proposer's best case
        # (summarization/RAG-shaped workloads).
        gram = rng.integers(10, cfg.vocab_size - 1, 8).tolist()
        reps = -(-a.prompt_len // len(gram))
        return [(gram * reps)[: a.prompt_len] for _ in range(a.requests)]
    return [
        rng.integers(10, cfg.vocab_size - 1, a.prompt_len).tolist()
        for _ in range(a.requests)
    ]


def run_load(engine, prompts, max_tokens, adapter_names=None):
    """Run all prompts concurrently; returns (gen_tokens, wall_s,
    ttft_s list) with TTFT measured client-side (submit -> first token),
    the same boundary an HTTP caller would see. With `adapter_names`,
    requests round-robin across the tenant adapters — the mixed-adapter
    packed batch the --adapters leg measures."""
    from substratus_tpu.serve.engine import Request

    done = []
    ttfts = []
    lock = threading.Lock()

    def run_one(p, adapter=None):
        req = engine.submit(Request(list(p), max_tokens=max_tokens,
                                    temperature=0.0, adapter=adapter))
        t0 = time.perf_counter()
        n = 0
        first = None
        while True:
            tok = req.out.get(timeout=600)
            if tok is None:
                break
            if first is None:
                first = time.perf_counter() - t0
            n += 1
        with lock:
            done.append(n)
            if first is not None:
                ttfts.append(first)

    t0 = time.perf_counter()
    threads = [
        threading.Thread(
            target=run_one,
            args=(
                p,
                adapter_names[i % len(adapter_names)]
                if adapter_names else None,
            ),
        )
        for i, p in enumerate(prompts)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return sum(done), time.perf_counter() - t0, ttfts


def make_engine(a, mesh=None, sync=None, role="both", handoff=None,
                max_batch=None, max_prefill_len=None, prefix_cache=True,
                overlap=None):
    """Config + random params + Engine, honoring the CLI knobs (shared by
    the single-process path and every gang worker — 'same config' is a
    code path, not a convention). role/handoff build the disaggregated
    split (--disagg leg); max_batch/max_prefill_len/prefix_cache
    override the derived values for legs that need a specific shape."""
    import jax

    from bench import random_quantized_params
    from substratus_tpu.models import llama
    from substratus_tpu.serve.engine import Engine, EngineConfig

    cfg = llama.CONFIGS[a.config]
    if a.config == "tiny":
        # The tiny test config needs f32 + a spare token id usable as a
        # never-emitted EOS (same setup tests/test_multihost_serving.py
        # uses); random-weight generations would otherwise stop on
        # accidental EOS hits and measure nothing.
        import jax.numpy as jnp

        cfg = cfg.replace(vocab_size=258, dtype=jnp.float32)
    if a.quantize == "none":
        params = llama.init_params(cfg, jax.random.key(0))
    else:
        params = jax.jit(
            lambda k: random_quantized_params(cfg, k, a.quantize)
        )(jax.random.key(0))
    jax.tree.leaves(params)[0].block_until_ready()

    adapters = None
    if getattr(a, "adapters", 0):
        # N random tenants packed into one engine (serve/adapters.py):
        # real nonzero A/B pairs so the per-row gather + rank-r einsums
        # cost what production adapters cost.
        import numpy as np

        from substratus_tpu.serve.adapters import AdapterStore
        from substratus_tpu.train.lora import init_lora

        rank = 8
        adapters = AdapterStore(
            cfg, capacity=a.adapters, rank=rank, dtype=cfg.dtype
        )
        for i in range(a.adapters):
            tree = init_lora(
                cfg, jax.random.key(100 + i), rank=rank, alpha=2 * rank,
                dtype=cfg.dtype,
            )
            for name in tree:
                tree[name]["b"] = (
                    jax.random.normal(
                        jax.random.key(200 + i), tree[name]["b"].shape
                    ) * 0.01
                )
            adapters.install(
                f"tenant-{i}",
                jax.tree.map(np.asarray, tree),
                scale=2.0,
            )

    ec = EngineConfig(
        max_batch=max_batch or a.batch,
        max_seq_len=min(a.max_seq_len, cfg.max_seq_len),
        max_prefill_len=max_prefill_len or min(256, a.max_seq_len),
        kv_cache_dtype="model" if a.config == "tiny" else a.kv_dtype,
        kv_layout=a.kv_layout,
        spec_k=a.spec_k,
        eos_token_id=257 if a.config == "tiny" else 2,
        step_floor_s=a.step_floor_ms / 1e3,
        role=role,
        prefix_cache=prefix_cache,
        overlap=overlap,
    )
    engine = Engine(cfg, params, ec, mesh=mesh, sync=sync, adapters=adapters,
                    handoff=handoff)
    engine.start()
    return cfg, engine


def measure(a, mesh=None, sync=None) -> dict:
    """One engine, the full load; returns the result record (leader-side
    fields only meaningful on the process that owns the requests)."""
    cfg, engine = make_engine(a, mesh=mesh, sync=sync)
    prompts = build_prompts(a, cfg)

    # Warm the executables (prefill bucket + decode) outside the clock.
    engine.generate(prompts[0][:16], max_tokens=2, temperature=0.0)

    admission = None
    if a.long_admission:
        # The >=8k-token admission leg: ONE long prompt, timed separately
        # — its JSON-encoded event broadcast must overflow StepSync's
        # 1 KB inline buffer onto the bucket-padded second collective.
        import numpy as np

        rng = np.random.default_rng(7)
        long_prompt = rng.integers(
            10, cfg.vocab_size - 1, a.long_admission
        ).tolist()
        before = len(engine.sync.timings) if engine.sync else 0
        t0 = time.perf_counter()
        engine.generate(long_prompt, max_tokens=2, temperature=0.0)
        wall = time.perf_counter() - t0
        admission = {
            "prompt_tokens": a.long_admission,
            "wall_ms": round(wall * 1e3, 3),
        }
        if engine.sync:
            # The admission-carrying broadcast is the biggest message in
            # the window this request spans.
            window = list(engine.sync.timings)[before:]
            if window:
                nbytes, secs = max(window, key=lambda t: t[0])
                admission["broadcast_bytes"] = nbytes
                admission["broadcast_ms"] = round(secs * 1e3, 3)

    adapter_names = (
        [f"tenant-{i}" for i in range(a.adapters)]
        if getattr(a, "adapters", 0) else None
    )
    gen_tokens, wall_s, ttfts = run_load(
        engine, prompts, a.max_tokens, adapter_names
    )
    out = {
        "gen_tokens": gen_tokens,
        "wall_s": round(wall_s, 3),
        "gen_tok_s": round(gen_tokens / wall_s, 1),
        "total_tok_s": round(
            (gen_tokens + a.requests * a.prompt_len) / wall_s, 1
        ),
        "ttft_ms": _percentiles_ms(ttfts),
        "admission": admission,
    }
    if a.spec_k:
        s = engine.stats
        out["spec"] = {
            "spec_k": a.spec_k,
            "acceptance": round(
                s["spec_accepted"] / s["spec_proposed"], 3
            ) if s["spec_proposed"] else 0.0,
            "verify_passes": s["verify_passes"],
        }
    if engine.sync is not None:
        out["broadcast_ms"] = _percentiles_ms(
            [secs for _, secs in engine.sync.timings]
        )
        out["broadcast_max_bytes"] = max(
            (b for b, _ in engine.sync.timings), default=0
        )
    engine.stop()
    return out


def gang_worker(a) -> int:
    """One process of the lockstep gang (leader owns the load)."""
    if a.transport == "tcp":
        # No shared XLA world: every process computes a full replica on
        # its own devices, mirrored by the lockstep scheduler over a TCP
        # event stream (serve/multihost.py TcpSync). The control plane —
        # serialization, a real inter-process hop per iteration, the
        # mirrored scheduler — is identical to production; only the
        # sharded math and ICI transfer need the XLA transport.
        from substratus_tpu.serve.multihost import TcpSync

        mesh = None
        sync = TcpSync(a.pid, a.nprocs, a.sync_port)
    else:
        import jax

        jax.distributed.initialize(
            coordinator_address=a.coord,
            num_processes=a.nprocs,
            process_id=a.pid,
        )
        from substratus_tpu.parallel.mesh import build_mesh
        from substratus_tpu.serve.multihost import StepSync

        # data spans the gang, tensor spans each process's local devices
        # — the shape tests/test_multihost_serving.py proves token-exact.
        mesh = build_mesh(data=a.nprocs, tensor=-1)
        sync = StepSync()
    if sync.leader:
        result = measure(a, mesh=mesh, sync=sync)
        result["leader"] = True
    else:
        cfg, engine = make_engine(a, mesh=mesh, sync=sync)
        engine._thread.join(timeout=3600)
        result = {
            "leader": False,
            "stopped": not engine._thread.is_alive(),
            "error": repr(engine.error) if engine.error else None,
            "broadcast_ms": _percentiles_ms(
                [secs for _, secs in sync.timings]
            ),
        }
    with open(a.out, "w") as f:
        json.dump(result, f)
    print("gang worker done", a.pid, flush=True)
    return 0


def run_gang(a, base_args) -> dict:
    """Spawn the N-process gang of this script, return the leader's
    record (follower clean-exit asserted)."""
    import socket
    import tempfile

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        sync_port = s.getsockname()[1]
    env = dict(os.environ)
    # Virtual CPU devices per process (ignored on real accelerators,
    # where each host's local chips are its devices).
    if env.get("JAX_PLATFORMS", "") == "cpu":
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={a.devs_per_proc}"
        )
    tmp = tempfile.mkdtemp(prefix="engine_bench_gang_")
    procs, outs = [], []
    for pid in range(a.gang):
        out = os.path.join(tmp, f"gang{pid}.json")
        outs.append(out)
        procs.append(
            subprocess.Popen(
                [
                    sys.executable, os.path.abspath(__file__), *base_args,
                    "--gang-worker", "--pid", str(pid),
                    "--nprocs", str(a.gang),
                    "--coord", f"127.0.0.1:{port}",
                    "--sync-port", str(sync_port), "--out", out,
                ],
                env=env, stdout=sys.stderr, stderr=subprocess.STDOUT,
            )
        )
    results = []
    try:
        for p, out in zip(procs, outs):
            rc = p.wait(timeout=a.gang_timeout)
            if rc != 0:
                raise SystemExit(f"gang worker failed rc={rc}")
            with open(out) as f:
                results.append(json.load(f))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    leader = next(r for r in results if r["leader"])
    for r in results:
        if not r["leader"]:
            assert r["stopped"] and not r["error"], r
    return leader


def run_single_same_shape(a, base_args) -> dict:
    """The single-process comparison engine over the SAME device count
    and mesh shape (so the delta isolates the lockstep control plane,
    not a different parallel layout). Runs as a subprocess because the
    parent must not initialize a jax backend before spawning workers."""
    env = dict(os.environ)
    if a.transport == "tcp":
        # TCP gang processes each hold a full replica on their own
        # devices — the fair single-process comparison is one engine
        # with the same per-process resources, no mesh.
        n = a.devs_per_proc
        extra = []
    else:
        n = a.gang * a.devs_per_proc
        extra = ["--mesh", f"data={a.gang},tensor=-1"]
    if env.get("JAX_PLATFORMS", "") == "cpu":
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    proc = subprocess.run(
        [
            sys.executable, os.path.abspath(__file__), *base_args,
            *extra, "--json-only",
        ],
        env=env, capture_output=True, text=True, timeout=a.gang_timeout,
    )
    sys.stderr.write(proc.stderr)
    if proc.returncode != 0:
        raise SystemExit(f"single-process comparison failed rc={proc.returncode}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def serve_worker(a) -> int:
    """One HTTP replica for the gateway leg: the same engine make_engine
    builds, behind the real serving app on 127.0.0.1:<port>. SIGTERM
    drains gracefully (serve/server.py) — the parent's terminate() at
    the end of the leg is the clean path, its kill during chaos is not."""
    from substratus_tpu.serve.server import ServerState, serve_forever
    from substratus_tpu.serve.tokenizer import ByteTokenizer

    _, engine = make_engine(a)
    state = ServerState(engine, ByteTokenizer(), a.config)
    print(f"replica on 127.0.0.1:{a.port}", flush=True)
    serve_forever(state, host="127.0.0.1", port=a.port, drain_grace_s=5.0)
    return 0


def _await_ready(url: str, timeout_s: float = 180.0) -> None:
    import urllib.error
    import urllib.request

    t0 = time.perf_counter()
    while time.perf_counter() - t0 < timeout_s:
        try:
            with urllib.request.urlopen(url + "/", timeout=2) as r:
                if r.status == 200:
                    return
        except (OSError, urllib.error.URLError):
            pass
        time.sleep(0.5)
    raise SystemExit(f"replica {url} never became ready")


async def _drive_http(base_url: str, a, n_requests: int) -> dict:
    """The HTTP load: a few sequential streaming requests for
    client-side TTFT, then the full non-streaming batch fired
    concurrently for aggregate throughput (completion_tokens summed
    from the usage blocks — the number the server actually produced)."""
    import string

    import aiohttp

    rng = __import__("random").Random(0)
    letters = string.ascii_letters + string.digits
    prompts = [
        "".join(rng.choice(letters) for _ in range(max(1, a.prompt_len - 1)))
        for _ in range(n_requests)
    ]

    async with aiohttp.ClientSession() as session:

        async def warm(p):
            async with session.post(
                base_url + "/v1/completions",
                json={"prompt": p, "max_tokens": 2, "temperature": 0.0},
            ) as r:
                await r.read()

        # Warm every replica's executables outside the clock: fire
        # 2x the replica count so p2c routing touches them all.
        await asyncio.gather(*(warm(p) for p in prompts[:4]))

        ttfts = []
        for p in prompts[:3]:
            t0 = time.perf_counter()
            async with session.post(
                base_url + "/v1/completions",
                json={"prompt": p, "max_tokens": a.max_tokens,
                      "temperature": 0.0, "stream": True},
            ) as r:
                async for line in r.content:
                    if line.startswith(b"data:") and b"[DONE]" not in line:
                        ttfts.append(time.perf_counter() - t0)
                        break
                async for _ in r.content:
                    pass  # drain

        async def run_one(p) -> int:
            async with session.post(
                base_url + "/v1/completions",
                json={"prompt": p, "max_tokens": a.max_tokens,
                      "temperature": 0.0},
            ) as r:
                body = await r.json()
                if r.status != 200:
                    raise SystemExit(f"load request failed: {r.status} {body}")
                return int(body["usage"]["completion_tokens"])

        t0 = time.perf_counter()
        counts = await asyncio.gather(*(run_one(p) for p in prompts))
        wall = time.perf_counter() - t0
    return {
        "gen_tokens": int(sum(counts)),
        "wall_s": round(wall, 3),
        "gen_tok_s": round(sum(counts) / wall, 1),
        "ttft_ms": _percentiles_ms(ttfts),
    }


def run_gateway_leg(a, base_args) -> dict:
    """Routed-vs-direct comparison (ISSUE 5 acceptance): N replica
    server subprocesses behind an in-process gateway vs ONE identical
    replica addressed directly, same total request count. The parent
    stays jax-free — it routes and measures, the workers compute."""
    import socket

    from substratus_tpu.gateway.router import Gateway, GatewayConfig

    n_requests = max(a.requests, 2 * a.batch)

    def spawn(n):
        ports = []
        for _ in range(n):
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                ports.append(s.getsockname()[1])
        procs = [
            subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), *base_args,
                 "--serve-worker", "--port", str(p)],
                stdout=sys.stderr, stderr=subprocess.STDOUT,
            )
            for p in ports
        ]
        urls = [f"http://127.0.0.1:{p}" for p in ports]
        for u in urls:
            _await_ready(u)
        return procs, urls

    def reap(procs):
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=15)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()

    async def run_routed(urls) -> dict:
        from aiohttp import web

        from substratus_tpu.gateway.router import build_gateway_app

        gw = Gateway(urls, GatewayConfig(poll_interval=0.5))
        runner = web.AppRunner(build_gateway_app(gw))
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        try:
            return await _drive_http(
                f"http://127.0.0.1:{port}", a, n_requests
            )
        finally:
            await runner.cleanup()

    procs, urls = spawn(a.gateway)
    try:
        routed_result = asyncio.run(run_routed(urls))
    finally:
        reap(procs)

    procs, urls = spawn(1)
    try:
        direct_result = asyncio.run(
            _drive_http(urls[0], a, n_requests)
        )
    finally:
        reap(procs)

    ttft_routed = routed_result["ttft_ms"].get("p50")
    ttft_direct = direct_result["ttft_ms"].get("p50")
    return {
        "metric": f"{a.config.replace('-', '_')}_gateway_routed_throughput",
        "value": routed_result["gen_tok_s"],
        "unit": "gen_tokens/sec",
        "replicas": a.gateway,
        "requests": n_requests,
        "max_tokens": a.max_tokens,
        "step_floor_ms": a.step_floor_ms,
        "direct_value": direct_result["gen_tok_s"],
        "routed_vs_direct": (
            round(routed_result["gen_tok_s"] / direct_result["gen_tok_s"], 3)
            if direct_result["gen_tok_s"] else None
        ),
        "ttft_p50_ms": ttft_routed,
        "ttft_p50_ms_direct": ttft_direct,
        "ttft_delta_ms": (
            round(ttft_routed - ttft_direct, 3)
            if ttft_routed is not None and ttft_direct is not None
            else None
        ),
        "wall_s": routed_result["wall_s"],
        "wall_s_direct": direct_result["wall_s"],
    }


def _timestamped_load(engines, prompts, max_tokens):
    """Run prompts round-robin across `engines`, recording a wall-clock
    timestamp per received token. Returns per-request dicts
    {first, ts: [t0, t1, ...], n} (ts includes the first token)."""
    from substratus_tpu.serve.engine import Request

    # Mutated in place so the caller can watch progress live (the
    # burst must land while the ongoing decodes are mid-flight).
    records = [{"ts": [], "n": 0} for _ in prompts]

    def run_one(i, p):
        eng = engines[i % len(engines)]
        req = eng.submit(
            Request(list(p), max_tokens=max_tokens, temperature=0.0)
        )
        rec = records[i]
        while True:
            tok = req.out.get(timeout=600)
            if tok is None:
                break
            rec["ts"].append(time.perf_counter())
        rec["n"] = len(rec["ts"])

    threads = [
        threading.Thread(target=run_one, args=(i, p))
        for i, p in enumerate(prompts)
    ]
    for t in threads:
        t.start()
    return threads, records


def _burst_drive(engines, a):
    """The prompt-burst workload (disagg acceptance): ongoing decodes
    start first; once they flow, a burst of long prompts lands. Returns
    (p99 inter-token ms of the ongoing decodes DURING the burst window,
    aggregate gen tok/s, total tokens, wall_s)."""
    import numpy as np

    rng = np.random.default_rng(3)
    vocab = 250
    n_ongoing = a.disagg_ongoing
    ongoing_prompts = [
        rng.integers(10, vocab, 16).tolist() for _ in range(n_ongoing)
    ]
    burst_prompts = [
        rng.integers(10, vocab, a.disagg_burst_prompt).tolist()
        for _ in range(a.disagg_burst)
    ]

    t0 = time.perf_counter()
    threads, ongoing = _timestamped_load(
        engines, ongoing_prompts, a.disagg_ongoing_tokens
    )
    # Wait until every ongoing request is decoding (has >= 2 tokens
    # flowing) before firing the burst, so the burst hits steady decode.
    deadline = time.perf_counter() + 120
    while time.perf_counter() < deadline:
        live = [r for r in ongoing if len(r["ts"]) >= 2]
        if len(live) == len(ongoing):
            break
        time.sleep(0.01)
    burst_t0 = time.perf_counter()
    bthreads, burst = _timestamped_load(engines, burst_prompts, 8)
    for t in bthreads:
        t.join()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    # The contention window: burst submission until the last burst
    # request got its first token (i.e. every prefill completed).
    burst_t1 = max(
        (r["ts"][0] for r in burst if r and r["ts"]), default=burst_t0
    )
    gaps = []
    for r in ongoing:
        ts = r["ts"]
        for prev, cur in zip(ts, ts[1:]):
            if burst_t0 <= cur <= burst_t1:
                gaps.append(cur - prev)
    total = sum(r["n"] for r in ongoing) + sum(r["n"] for r in burst)
    p99 = _percentiles_ms(gaps).get("p99")
    return p99, round(total / wall, 1), total, round(wall, 3)


def run_disagg_leg(a) -> dict:
    """Disaggregated pair vs monolithic pair (ISSUE 7 acceptance): one
    prefill + one decode engine joined by the real TCP KV-handoff
    transport, against two monolithic engines — same model instances,
    same total decode slots, same simulated device step — under the
    prompt-burst workload. The number that matters is p99 inter-token
    latency of the ONGOING decodes while the burst prefills: monolithic
    engines stall their decode batch for every prefill chunk; the
    decode tier never prefills."""
    from substratus_tpu.serve.disagg import (
        HandoffManager,
        HandoffServer,
        PoolSpec,
    )

    decode_slots = 2 * a.batch  # == the monolithic pair's total
    chunk = a.disagg_chunk

    # Disaggregated pair: all client traffic enters the prefill engine.
    _, dec = make_engine(
        a, role="decode", max_batch=decode_slots, max_prefill_len=chunk
    )
    srv = HandoffServer(dec, host="127.0.0.1")
    mgr = HandoffManager(
        [f"127.0.0.1:{srv.port}"],
        PoolSpec.from_engine(dec),
    )
    _, pre = make_engine(
        a, role="prefill", handoff=mgr, max_batch=decode_slots,
        max_prefill_len=chunk,
    )
    pre.generate([10] * 8, max_tokens=2)  # warm executables off-clock
    d_p99, d_toks, d_total, d_wall = _burst_drive([pre], a)
    handoffs = pre.stats["handoffs"]
    pre.stop()
    dec.stop()
    srv.close()
    mgr.close()

    # Monolithic pair: the same load round-robined across two engines.
    monos = []
    for _ in range(2):
        _, eng = make_engine(a, max_prefill_len=chunk)
        eng.generate([10] * 8, max_tokens=2)
        monos.append(eng)
    m_p99, m_toks, m_total, m_wall = _burst_drive(monos, a)
    for eng in monos:
        eng.stop()

    return {
        "metric": f"{a.config.replace('-', '_')}_disagg_burst_p99_inter_token",
        "value": d_p99,
        "unit": "ms",
        "mono_value": m_p99,
        "p99_vs_mono": (
            round(d_p99 / m_p99, 3) if d_p99 and m_p99 else None
        ),
        "gen_tok_s": d_toks,
        "mono_gen_tok_s": m_toks,
        "tok_s_vs_mono": round(d_toks / m_toks, 3) if m_toks else None,
        "gen_tokens": d_total,
        "mono_gen_tokens": m_total,
        "wall_s": d_wall,
        "mono_wall_s": m_wall,
        "handoffs": handoffs,
        "ongoing": a.disagg_ongoing,
        "burst": a.disagg_burst,
        "burst_prompt_tokens": a.disagg_burst_prompt,
        "step_floor_ms": a.step_floor_ms,
        "decode_slots": decode_slots,
    }


def run_batchgen_leg(a) -> dict:
    """Batch-generation actor gang vs a single actor (ISSUE 9
    acceptance): N engines drain ONE shared prompt manifest through the
    continuous-refill driver (serve/batchgen.py) against one identical
    engine on the same manifest, same simulated device step. What the
    ratio measures on CPU is whether the driver keeps N actors
    concurrently busy with zero queue-wait refill; the occupancy number
    is the point of the architecture — the decode batch never drains
    while manifest records remain."""
    import tempfile

    import numpy as np

    from substratus_tpu.load.manifest import write_manifest
    from substratus_tpu.serve.batchgen import BatchGenDriver

    rng = np.random.default_rng(5)
    vocab = 250
    # Varied budgets stagger completions so refill is the steady drip
    # the scheduler handles every iteration, not a synchronized wave.
    records = [
        {
            "id": f"r{i}",
            "tokens": rng.integers(10, vocab, a.prompt_len).tolist(),
            "max_tokens": int(a.max_tokens + rng.integers(-4, 5)),
        }
        for i in range(a.requests)
    ]
    tmp = tempfile.mkdtemp(prefix="engine_bench_batchgen_")
    manifest = os.path.join(tmp, "prompts.jsonl")
    write_manifest(manifest, records)

    def drive(n_actors: int):
        engines = []
        for _ in range(n_actors):
            _, eng = make_engine(a)
            eng.generate([10] * 8, max_tokens=2)  # warm off-clock
            engines.append(eng)
        driver = BatchGenDriver(
            engines, manifest,
            os.path.join(tmp, f"out-{n_actors}"),
            max_tokens=a.max_tokens,
        )
        summary = driver.run()
        for eng in engines:
            eng.stop()
        if summary["written"] != len(records) or summary["errors"]:
            raise SystemExit(f"batchgen leg lost records: {summary}")
        return summary

    gang = drive(a.batchgen)
    single = drive(1)
    return {
        "metric": f"{a.config.replace('-', '_')}_batchgen_gang_throughput",
        "value": gang["gen_tok_s"],
        "unit": "gen_tokens/sec",
        "actors": a.batchgen,
        "single_value": single["gen_tok_s"],
        "gang_vs_single": (
            round(gang["gen_tok_s"] / single["gen_tok_s"], 3)
            if single["gen_tok_s"] else None
        ),
        "slot_occupancy": gang["slot_occupancy"],
        "single_slot_occupancy": single["slot_occupancy"],
        "records": len(records),
        "gen_tokens": gang["gen_tokens"],
        "max_tokens": a.max_tokens,
        "step_floor_ms": a.step_floor_ms,
        "batch": a.batch,
        "wall_s": gang["wall_s"],
        "single_wall_s": single["wall_s"],
    }


class _HostWorkSink:
    """Request.out stand-in whose put() does REAL per-token host work on
    the engine scheduler thread (put runs inside Engine._emit): it
    detokenizes the accumulated output `repeats` times — the serving
    path's detokenize + SSE-encode cost, concentrated at exactly the
    point the overlapped scheduler hides under the device step. A plain
    queue behind it keeps the waiter contract (terminal None)."""

    def __init__(self, tok, repeats: int):
        import queue as _q

        self.tok = tok
        self.repeats = repeats
        self.ids = []
        self.ts = []  # per-token arrival timestamps (scheduler-side)
        self.q = _q.Queue()

    def put(self, item, block=True, timeout=None):
        if item is not None:
            self.ids.append(int(item))
            for _ in range(self.repeats):
                self.tok.decode(self.ids)
            self.ts.append(time.perf_counter())
        self.q.put(item)

    def get(self, block=True, timeout=None):
        return self.q.get(block, timeout)


def _calibrate_detok_repeats(tok, target_s: float, n_ids: int) -> int:
    """How many decode() passes over an n_ids-token tail cost ~target_s
    on THIS host. Measured, not assumed — the bench's host work must be
    a fixed wall-time fraction of the simulated device step regardless
    of the runner's single-core speed."""
    ids = list(range(10, 10 + n_ids))
    t0 = time.perf_counter()
    reps = 0
    while time.perf_counter() - t0 < 0.05:
        tok.decode(ids)
        reps += 1
    one = (time.perf_counter() - t0) / max(1, reps)
    return max(1, int(target_s / one))


def _overlap_drive(a, overlap: bool, repeats: int) -> dict:
    """One engine, one full-batch wave of greedy requests with host-work
    sinks; returns steady-state inter-token stats + aggregate tok/s."""
    from substratus_tpu.serve.engine import Request
    from substratus_tpu.serve.tokenizer import ByteTokenizer

    cfg, eng = make_engine(a, overlap=overlap)
    tok = ByteTokenizer()
    # Honors --repetitive (the spec leg's lookup-friendly shape); the
    # plain overlap leg keeps its random prompts.
    prompts = build_prompts(a, cfg)
    # Warm prefill + decode executables outside the clock.
    eng.generate(prompts[0][:8], max_tokens=3, temperature=0.0)
    if a.spec_k:
        # Spec engines JIT one verify executable per round width
        # (width = max per-stream draft length + 1, so the adaptive
        # planner visits several): run a full-batch warm wave so every
        # width compiles outside the clock — a single 3-token generate
        # leaves ~1s compile spikes inside the measured wave. Then zero
        # the spec counters so the record's acceptance reflects the
        # measured wave only.
        warm = [
            eng.submit(Request(list(p), max_tokens=min(24, a.max_tokens),
                               temperature=0.0))
            for p in prompts
        ]
        for r in warm:
            while r.out.get(timeout=600) is not None:
                pass
        for k in ("spec_proposed", "spec_accepted", "verify_passes"):
            eng.stats[k] = 0

    sinks = []
    t0 = time.perf_counter()
    reqs = []
    for p in prompts:
        sink = _HostWorkSink(tok, repeats)
        sinks.append(sink)
        reqs.append(
            eng.submit(
                Request(list(p), max_tokens=a.max_tokens,
                        temperature=0.0, out=sink)
            )
        )
    for r in reqs:
        while r.out.get(timeout=600) is not None:
            pass
    wall = time.perf_counter() - t0
    outputs = [list(s.ids) for s in sinks]
    gen = sum(len(ids) for ids in outputs)
    gaps = []
    for s in sinks:
        ts = s.ts
        # Steady state: skip each stream's first gaps (admission wave,
        # first-compile iteration) — the claim under test is the
        # per-token cadence once the batch decodes continuously.
        for prev, cur in zip(ts[3:], ts[4:]):
            gaps.append(cur - prev)
    eng.stop()
    # Bubble attribution (observability/timeline.py): per-cause seconds
    # above the device floor, over STEADY-STATE iterations only
    # (admission iterations pay prefill floors by design; the claim
    # under test is the decode cadence, same window as `gaps`).
    steady = [
        r for r in eng.timeline.records()
        if not r["admitted"] and r["active_slots"]
    ]
    bubble_by_cause: dict = {}
    for r in steady:
        for cause, sec in r["bubble"].items():
            bubble_by_cause[cause] = bubble_by_cause.get(cause, 0.0) + sec
    gap_s = sum(r["gap_s"] for r in steady)
    attributed_s = sum(bubble_by_cause.values())
    mean_ms = (
        round(sum(gaps) / len(gaps) * 1e3, 3) if gaps else None
    )
    stats = {k: int(v) for k, v in eng.stats.items()}
    return {
        "inter_token_mean_ms": mean_ms,
        "inter_token_ms": _percentiles_ms(gaps),
        "gen_tok_s": round(gen / wall, 1),
        "gen_tokens": gen,
        "wall_s": round(wall, 3),
        "outputs": outputs,
        "stats": stats,
        "bubble": {
            "steps": len(steady),
            "by_cause_s": {
                c: round(v, 6) for c, v in sorted(bubble_by_cause.items())
            },
            "attributed_s": round(attributed_s, 6),
            "gap_s": round(gap_s, 6),
        },
    }


def run_overlap_leg(a) -> dict:
    """Overlapped vs synchronous scheduler on the same shape (ISSUE 10
    acceptance): one full-batch greedy wave, a nonzero simulated device
    step, and deliberate per-token host work (real detokenize in the
    emit path, scheduler-thread side). The synchronous engine pays
    device_step + host_work per token; the overlapped engine does the
    host work while the next step runs, so its steady-state inter-token
    mean must sit at ~the device floor (<= 1.15x) at equal-or-better
    aggregate tok/s — and greedy outputs must match token for token."""
    # One static wave: admissions mid-run would pay prefill floors
    # inside the steady-state window and measure scheduling noise.
    a.requests = min(a.requests, a.batch)
    if not a.step_floor_ms:
        # The leg is meaningless without a device-step model: with an
        # instant step there is nothing to hide host work under.
        a.step_floor_ms = 15.0
    floor_s = a.step_floor_ms / 1e3
    from substratus_tpu.serve.tokenizer import ByteTokenizer

    # Host work per STEP targets ~half the device floor, split across
    # the batch's per-token emits: big enough that the synchronous
    # baseline visibly pays it (~1.4-1.8x floor), small enough that the
    # overlapped pipeline can hide all of it under the step.
    per_token_s = (floor_s * a.overlap_host_frac) / max(1, a.requests)
    repeats = _calibrate_detok_repeats(
        ByteTokenizer(), per_token_s, a.max_tokens // 2
    )
    sync_r = _overlap_drive(a, overlap=False, repeats=repeats)
    over_r = _overlap_drive(a, overlap=True, repeats=repeats)
    if over_r.pop("outputs") != sync_r.pop("outputs"):
        raise SystemExit(
            "overlap leg: greedy outputs diverged between the "
            "overlapped and synchronous schedulers"
        )
    mean_over = over_r["inter_token_mean_ms"]
    mean_sync = sync_r["inter_token_mean_ms"]
    # Bubble-attribution gates (ISSUE 11): the bubble ratio is the
    # attributed time above the device floor per floor-second — the
    # engine-side restatement of the 1.15x inter-token acceptance, but
    # CAUSED: a host-path regression shows up as host_overrun seconds
    # and fails `make overlap-bench` here instead of eroding the floor
    # silently. attributed_frac gates the attribution machinery itself
    # (>90% of the measured gap must carry a cause).
    bub = over_r["bubble"]
    floor_total = bub["steps"] * floor_s
    bubble_ratio = (
        round(bub["attributed_s"] / floor_total, 4) if floor_total else None
    )
    # Guard the ratio against a near-perfect pipeline: with (gap <2% of
    # the floor budget) there is nothing to attribute and the fraction
    # is 0/0 noise.
    attributed_frac = (
        round(bub["attributed_s"] / bub["gap_s"], 4)
        if bub["gap_s"] > 0.02 * floor_total else 1.0
    )
    tok_ratio = (
        round(over_r["gen_tok_s"] / sync_r["gen_tok_s"], 3)
        if sync_r["gen_tok_s"] else None
    )
    gates = [
        {"name": "overlap_bubble_ratio", "value": bubble_ratio,
         "max": 0.15},
        {"name": "overlap_bubble_attributed_frac",
         "value": attributed_frac, "min": 0.9},
        {"name": "overlap_tok_s_vs_sync", "value": tok_ratio,
         "min": 0.95},
    ]
    return {
        "metric": f"{a.config.replace('-', '_')}_overlap_inter_token",
        "value": mean_over,
        "unit": "ms",
        "sync_value": mean_sync,
        "step_floor_ms": a.step_floor_ms,
        "overlap_vs_floor": (
            round(mean_over / a.step_floor_ms, 3)
            if mean_over and a.step_floor_ms else None
        ),
        "sync_vs_floor": (
            round(mean_sync / a.step_floor_ms, 3)
            if mean_sync and a.step_floor_ms else None
        ),
        "overlap_vs_sync": (
            round(mean_over / mean_sync, 3)
            if mean_over and mean_sync else None
        ),
        "gen_tok_s": over_r["gen_tok_s"],
        "sync_gen_tok_s": sync_r["gen_tok_s"],
        "tok_s_vs_sync": (
            round(over_r["gen_tok_s"] / sync_r["gen_tok_s"], 3)
            if sync_r["gen_tok_s"] else None
        ),
        "host_work_ms_per_token": round(per_token_s * 1e3, 3),
        "detok_repeats": repeats,
        "requests": a.requests,
        "max_tokens": a.max_tokens,
        "batch": a.batch,
        "inter_token_ms": over_r["inter_token_ms"],
        "sync_inter_token_ms": sync_r["inter_token_ms"],
        "wall_s": over_r["wall_s"],
        "sync_wall_s": sync_r["wall_s"],
        # Pipeline-bubble attribution (observability/timeline.py):
        # steady-state per-cause totals for both schedulers — the sync
        # engine's host_overrun is the cost the overlap hides.
        "bubble": bub,
        "sync_bubble": sync_r["bubble"],
        "bubble_ratio": bubble_ratio,
        "bubble_attributed_frac": attributed_frac,
        # Hard gates evaluated by hack/bench_compare.py --validate.
        "gates": gates,
    }


def _counter_total(name: str, label_frag: str = "") -> float:
    """Sum a counter's samples from the global registry's text render
    (filtered by a label fragment) — the same boundary Prometheus
    scrapes, so the bench gates what operators would see."""
    from substratus_tpu.observability.metrics import METRICS

    total = 0.0
    for line in METRICS.render().splitlines():
        if line.startswith(name) and label_frag in line:
            total += float(line.rsplit(" ", 1)[-1])
    return total


def run_spec_leg(a) -> dict:
    """Speculation x overlap composition (ISSUE 14 acceptance): four
    engines on the same repetitive-prompt shape — plain synchronous,
    spec-only, overlap-only, and spec+overlap — with the simulated
    device floor and the overlap leg's per-token host work. The
    composed engine must beat BOTH single-lever legs on aggregate
    tok/s (the two wins multiply instead of cancelling), greedy
    outputs must be token-exact across all four, and steady-state
    pipeline_flushes_total{reason="spec"} must not move (spec rounds
    chain on-device; the historical flush-per-round is retired)."""
    import copy

    from substratus_tpu.serve.tokenizer import ByteTokenizer

    # One static wave on the prompt-lookup proposer's hitting shape.
    a.requests = min(a.requests, a.batch)
    a.repetitive = True
    if not a.spec_k:
        a.spec_k = 3
    if not a.step_floor_ms:
        a.step_floor_ms = 15.0
    floor_s = a.step_floor_ms / 1e3
    per_token_s = (floor_s * a.overlap_host_frac) / max(1, a.requests)
    repeats = _calibrate_detok_repeats(
        ByteTokenizer(), per_token_s, a.max_tokens // 2
    )

    def drive(spec_k: int, overlap: bool) -> dict:
        v = copy.copy(a)
        v.spec_k = spec_k
        return _overlap_drive(v, overlap=overlap, repeats=repeats)

    flush_before = _counter_total(
        "substratus_serve_pipeline_flushes_total", 'reason="spec"'
    )
    plain = drive(0, overlap=False)
    spec_only = drive(a.spec_k, overlap=False)
    over_only = drive(0, overlap=True)
    both = drive(a.spec_k, overlap=True)
    flush_after = _counter_total(
        "substratus_serve_pipeline_flushes_total", 'reason="spec"'
    )

    ref = plain.pop("outputs")
    for name, r in (("spec-only", spec_only), ("overlap-only", over_only),
                    ("spec+overlap", both)):
        if r.pop("outputs") != ref:
            raise SystemExit(
                f"spec leg: greedy outputs diverged between the {name} "
                "and plain synchronous engines"
            )

    def ratio(x, y):
        return round(x / y, 3) if y else None

    spec_flush_delta = flush_after - flush_before
    gates = [
        # The composition gates: the two levers must multiply.
        {"name": "spec_overlap_tok_s_vs_spec_only",
         "value": ratio(both["gen_tok_s"], spec_only["gen_tok_s"]),
         "min": 1.0},
        {"name": "spec_overlap_tok_s_vs_overlap_only",
         "value": ratio(both["gen_tok_s"], over_only["gen_tok_s"]),
         "min": 1.0},
        # Retired-reason regression gate: spec rounds never flush.
        {"name": "spec_flush_delta", "value": spec_flush_delta,
         "max": 0.0},
    ]
    prop = both["stats"]["spec_proposed"]
    acc = both["stats"]["spec_accepted"]
    return {
        "metric": f"{a.config.replace('-', '_')}_spec_overlap_throughput",
        "value": both["gen_tok_s"],
        "unit": "gen_tokens/sec",
        "spec_k": a.spec_k,
        "step_floor_ms": a.step_floor_ms,
        "host_work_ms_per_token": round(per_token_s * 1e3, 3),
        "requests": a.requests,
        "max_tokens": a.max_tokens,
        "batch": a.batch,
        "plain_tok_s": plain["gen_tok_s"],
        "spec_only_tok_s": spec_only["gen_tok_s"],
        "overlap_only_tok_s": over_only["gen_tok_s"],
        "spec_overlap_tok_s": both["gen_tok_s"],
        "vs_plain": ratio(both["gen_tok_s"], plain["gen_tok_s"]),
        "vs_spec_only": ratio(both["gen_tok_s"], spec_only["gen_tok_s"]),
        "vs_overlap_only": ratio(both["gen_tok_s"], over_only["gen_tok_s"]),
        "acceptance": round(acc / prop, 3) if prop else None,
        "verify_passes": both["stats"]["verify_passes"],
        "spec_only_acceptance": (
            round(
                spec_only["stats"]["spec_accepted"]
                / spec_only["stats"]["spec_proposed"], 3,
            ) if spec_only["stats"]["spec_proposed"] else None
        ),
        "inter_token_ms": both["inter_token_ms"],
        "spec_flush_delta": spec_flush_delta,
        "wall_s": both["wall_s"],
        # Hard gates evaluated by hack/bench_compare.py --validate.
        "gates": gates,
    }


def run_prefix_reuse_leg(a) -> dict:
    """Shared-prefix reuse vs cold prefill (ROADMAP item 1 evidence):
    the same repeated-system-prompt workload against an engine with the
    prefix registry on and one with it off — TTFT is where reuse shows
    (chunks skipped are device steps not taken), aggregate tok/s must
    not regress."""
    import numpy as np

    rng = np.random.default_rng(11)
    vocab = 250
    chunk = a.prefix_chunk
    shared = rng.integers(10, vocab, a.prefix_len).tolist()
    prompts = [
        shared + rng.integers(10, vocab, 8).tolist()
        for _ in range(a.requests)
    ]

    def drive(prefix_cache: bool):
        _, eng = make_engine(
            a, max_prefill_len=chunk, prefix_cache=prefix_cache
        )
        # Warm every chunk-prefill shape off-clock with the full shared
        # prompt — this also registers the prefix on the reuse engine,
        # so the measurement is steady-state on both sides.
        eng.generate(list(prompts[0]), max_tokens=2)
        from substratus_tpu.serve.engine import Request

        ttfts, total = [], 0
        t0 = time.perf_counter()
        # Sequential: TTFT measures prefill cost, not queueing noise —
        # and lets the first request register the prefix for the rest.
        for p in prompts:
            req = eng.submit(
                Request(list(p), max_tokens=a.max_tokens, temperature=0.0)
            )
            t1 = time.perf_counter()
            first = None
            while True:
                tok = req.out.get(timeout=600)
                if tok is None:
                    break
                if first is None:
                    first = time.perf_counter() - t1
                total += 1
            ttfts.append(first)
        wall = time.perf_counter() - t0
        stats = dict(eng.stats)
        eng.stop()
        return ttfts, round(total / wall, 1), stats

    reuse_ttfts, reuse_toks, reuse_stats = drive(True)
    cold_ttfts, cold_toks, _ = drive(False)
    reuse_p50 = _percentiles_ms(reuse_ttfts).get("p50")
    cold_p50 = _percentiles_ms(cold_ttfts).get("p50")
    return {
        "metric": f"{a.config.replace('-', '_')}_prefix_reuse_ttft",
        "value": reuse_p50,
        "unit": "ms",
        "cold_value": cold_p50,
        "reuse_vs_cold_ttft": (
            round(reuse_p50 / cold_p50, 3)
            if reuse_p50 and cold_p50 else None
        ),
        "gen_tok_s": reuse_toks,
        "cold_gen_tok_s": cold_toks,
        "tok_s_vs_cold": (
            round(reuse_toks / cold_toks, 3) if cold_toks else None
        ),
        "prefix_hit_tokens": reuse_stats["prefix_hit_tokens"],
        "prefill_tokens": reuse_stats["prefill_tokens"],
        "requests": a.requests,
        "prefix_tokens": a.prefix_len,
        "step_floor_ms": a.step_floor_ms,
    }


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="llama2-7b")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--max-tokens", type=int, default=64)
    ap.add_argument("--batch", type=int, default=24)
    ap.add_argument("--max-seq-len", type=int, default=512)
    ap.add_argument("--kv-dtype", default="int8", choices=["int8", "model"])
    ap.add_argument(
        "--quantize", default="int8", choices=["int8", "int4", "none"],
        help="weight quantization for the random params (none = the "
             "model dtype, what the tiny smoke config uses)",
    )
    ap.add_argument(
        "--kv-layout", default="auto", choices=["auto", "paged", "dense"]
    )
    ap.add_argument(
        "--spec-k", type=int, default=0,
        help="prompt-lookup speculation (repetitive prompts benefit)",
    )
    ap.add_argument(
        "--repetitive", action="store_true",
        help="prompts made of repeated n-grams so lookup speculation hits",
    )
    ap.add_argument(
        "--gang", type=int, default=0,
        help="N-process lockstep gang vs a single engine of the same "
             "mesh shape; prints the combined comparison JSON",
    )
    ap.add_argument(
        "--adapters", type=int, default=0,
        help="pack N random LoRA tenants into one engine and run the "
             "mixed-adapter load round-robin vs an identical base-only "
             "engine on the same shape; prints the packed-vs-base JSON "
             "(substratus_tpu/serve/adapters.py, docs/serving.md)",
    )
    ap.add_argument(
        "--gateway", type=int, default=0,
        help="N replica HTTP servers behind the routing gateway vs one "
             "direct replica; prints the routed-vs-direct JSON "
             "(substratus_tpu/gateway, docs/serving.md)",
    )
    ap.add_argument(
        "--disagg", action="store_true",
        help="disaggregated 1-prefill + 1-decode pair (real TCP KV "
             "handoff, serve/disagg.py) vs 2 monolithic engines under a "
             "prompt-burst workload; prints burst-window p99 inter-token "
             "latency and aggregate tok/s for both (docs/serving.md)",
    )
    ap.add_argument("--disagg-ongoing", type=int, default=6,
                    help="ongoing decode requests the burst disturbs")
    ap.add_argument("--disagg-ongoing-tokens", type=int, default=96)
    ap.add_argument("--disagg-burst", type=int, default=4,
                    help="long prompts fired mid-decode")
    ap.add_argument("--disagg-burst-prompt", type=int, default=160)
    ap.add_argument("--disagg-chunk", type=int, default=32,
                    help="prefill chunk length (each chunk pays the "
                         "simulated device step)")
    ap.add_argument(
        "--batchgen", type=int, default=0,
        help="N-actor batch-generation gang vs one actor on the same "
             "shared prompt manifest (serve/batchgen.py continuous-"
             "refill driver): aggregate gen tok/s ratio + steady-state "
             "decode slot occupancy (docs/batch-generation.md)",
    )
    ap.add_argument(
        "--overlap", action="store_true",
        help="overlapped vs synchronous decode scheduler on the same "
             "shape at a nonzero --step-floor-ms with real per-token "
             "detokenize host work in the emit path: steady-state "
             "inter-token mean for both + aggregate tok/s + greedy "
             "token parity (serve/engine.py one-step-ahead dispatch, "
             "docs/performance.md)",
    )
    ap.add_argument(
        "--overlap-host-frac", type=float, default=0.5,
        dest="overlap_host_frac",
        help="per-STEP host work as a fraction of the device-step floor "
             "for the --overlap leg (split across the batch's emits)",
    )
    ap.add_argument(
        "--spec-overlap", action="store_true", dest="spec_overlap",
        help="speculation x overlap composition: plain / spec-only / "
             "overlap-only / spec+overlap engines on the same "
             "repetitive-prompt shape at a nonzero --step-floor-ms; "
             "hard gates require the composed engine to beat both "
             "single-lever legs at token-exact greedy parity with zero "
             "spec pipeline flushes (serve/engine.py _spec_dispatch/"
             "_spec_drain, docs/performance.md)",
    )
    ap.add_argument(
        "--prefix-reuse", action="store_true",
        help="repeated-shared-prefix workload vs cold prefill on the "
             "same shape: TTFT win + aggregate tok/s (ROADMAP item 1 "
             "evidence; the radix/COW reuse lives in serve/engine.py "
             "_admit_paged)",
    )
    ap.add_argument("--prefix-len", type=int, default=96,
                    help="shared prefix length in tokens")
    ap.add_argument("--prefix-chunk", type=int, default=32,
                    help="prefill chunk length for the prefix leg")
    ap.add_argument(
        "--long-admission", type=int, default=0,
        help="extra leg: one prompt of this many tokens, its admission "
             "broadcast (JSON-encoded prompt) timed separately — use "
             ">=8192 to exercise the overflow collective",
    )
    ap.add_argument(
        "--devs-per-proc", type=int, default=2,
        help="virtual CPU devices per gang process (CPU runs only)",
    )
    ap.add_argument(
        "--transport", default="xla", choices=["xla", "tcp"],
        help="gang event transport: xla = the production "
             "multihost_utils collective (needs a backend with "
             "multi-process support); tcp = TcpSync full-replica gang "
             "(works on any backend, incl. CPU jaxlib without "
             "multi-process collectives)",
    )
    ap.add_argument("--gang-timeout", type=float, default=1200.0)
    ap.add_argument(
        "--step-floor-ms", type=float, default=0.0,
        help="minimum wall time per decode iteration — simulates "
             "accelerator step latency on CPU hosts so concurrency "
             "benches measure the control plane, not the core count "
             "(0 = off; the --gateway smoke defaults it to 15)",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="CPU-scaled CI smoke: tiny config, small load",
    )
    ap.add_argument(
        "--mesh", default="",
        help="mesh spec 'data=2,tensor=-1' for the single-process engine "
             "(internal: the gang's same-shape comparison)",
    )
    ap.add_argument(
        "--json-only", action="store_true",
        help="print only the raw result record (internal)",
    )
    # gang-worker / gateway-replica internals
    ap.add_argument("--gang-worker", action="store_true")
    ap.add_argument("--serve-worker", action="store_true")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--pid", type=int, default=0)
    ap.add_argument("--nprocs", type=int, default=0)
    ap.add_argument("--coord", default="")
    ap.add_argument("--sync-port", type=int, default=0)
    ap.add_argument("--out", default="")
    a = ap.parse_args(argv)
    if a.smoke:
        a.config = "tiny"
        a.quantize = "none"
        a.prompt_len = min(a.prompt_len, 16)
        a.batch = min(a.batch, 4)
        a.max_seq_len = min(a.max_seq_len, 128)
        if a.gateway or a.serve_worker:
            # The gateway smoke shape (ISSUE 5 acceptance): enough
            # same-length requests to need full waves on every replica
            # (2 waves routed, 4 direct), decode long enough to
            # dominate HTTP/prefill overhead, and a simulated device
            # step so 'can the gateway keep 2 replicas busy at once'
            # is what the ratio measures on any host.
            a.requests = min(a.requests, 4 * a.batch)
            a.max_tokens = min(a.max_tokens, 48)
            if not a.step_floor_ms:
                a.step_floor_ms = 15.0
        elif a.adapters:
            # The adapter-packing smoke (ISSUE 6 acceptance): a mixed
            # 4-tenant batch vs base-only on the same shape, decode
            # long enough to dominate prefill, simulated device step so
            # the ratio measures the packed program's per-iteration
            # cost (the gather + rank-r einsums), not host core count.
            a.requests = min(a.requests, 2 * a.batch)
            a.max_tokens = min(a.max_tokens, 32)
            if not a.step_floor_ms:
                a.step_floor_ms = 15.0
        elif a.disagg:
            # The disaggregation smoke (ISSUE 7 acceptance): burst
            # prompts long enough for several prefill chunks (each
            # paying the simulated device step — the decode-stalling
            # contention a monolithic engine can't avoid), a context
            # window that fits prompt+generation, and enough ongoing
            # decodes to make the inter-token histogram meaningful.
            a.max_seq_len = 256
            if not a.step_floor_ms:
                a.step_floor_ms = 15.0
        elif a.prefix_reuse:
            # The prefix-reuse smoke (ROADMAP item 1 evidence): a
            # shared prefix spanning several prefill chunks, so a
            # registry hit skips real (simulated) device steps.
            a.max_tokens = min(a.max_tokens, 8)
            a.requests = min(a.requests, 8)
            if not a.step_floor_ms:
                a.step_floor_ms = 15.0
        elif a.spec_overlap:
            # The speculation-composition smoke (ISSUE 14 acceptance):
            # the overlap smoke shape plus the lookup proposer's
            # repetitive prompts, decode long enough that acceptance
            # (and the adaptive-k EWMA) reaches steady state. The
            # simulated floor is what speculation amortizes — one
            # (k+1)-wide verify pays the floor once for up to k+1
            # tokens — so the composed win is measurable on any host.
            # The horizon is LONGER than the overlap smoke: the tiny
            # random model's greedy trajectory settles into the
            # repeated runs lookup speculation feeds on only after the
            # first few dozen tokens, and the acceptance steady state
            # is what the composition gates measure.
            a.batch = min(a.batch, 4)
            a.requests = a.batch
            a.max_tokens = 96
            a.max_seq_len = 128
            if not a.step_floor_ms:
                a.step_floor_ms = 15.0
        elif a.overlap:
            # The overlapped-scheduler smoke (ISSUE 10 acceptance): one
            # full-batch wave decoding long enough for a clean steady
            # state, the simulated device step, and host work pinned to
            # ~half the floor — synchronous pays floor + host work
            # (~1.4-1.8x floor on this shape), overlapped must hold
            # <= 1.15x floor at equal-or-better aggregate tok/s
            # (tests/test_overlap.py asserts; this leg captures).
            a.batch = min(a.batch, 4)
            a.requests = a.batch
            a.max_tokens = min(a.max_tokens, 48)
            if not a.step_floor_ms:
                a.step_floor_ms = 15.0
        elif a.batchgen:
            # The batch-generation smoke (ISSUE 9 acceptance): enough
            # records for many full refill waves per actor, decode
            # dominating prefill, and the simulated device step so the
            # ratio measures whether the refill driver keeps N actors
            # busy — not the host's core count. Acceptance: 2-actor
            # >= 1.8x one actor AND steady occupancy >= 0.9
            # (tests/test_batchgen.py asserts both; the make target
            # validates the capture schema).
            a.prompt_len = min(a.prompt_len, 16)
            a.requests = min(a.requests, 10 * a.batch)
            a.max_tokens = min(a.max_tokens, 32)
            if not a.step_floor_ms:
                a.step_floor_ms = 15.0
        else:
            a.requests = min(a.requests, 6)
            a.max_tokens = min(a.max_tokens, 8)
    return a


# Args every sub-invocation must inherit (everything but the mode flags).
def passthrough_args(a) -> list:
    out = [
        "--config", a.config, "--requests", str(a.requests),
        "--prompt-len", str(a.prompt_len), "--max-tokens",
        str(a.max_tokens), "--batch", str(a.batch),
        "--max-seq-len", str(a.max_seq_len), "--kv-dtype", a.kv_dtype,
        "--quantize", a.quantize, "--kv-layout", a.kv_layout,
        "--spec-k", str(a.spec_k),
        "--devs-per-proc", str(a.devs_per_proc),
        "--long-admission", str(a.long_admission),
        "--transport", a.transport,
        "--step-floor-ms", str(a.step_floor_ms),
    ]
    if a.repetitive:
        out.append("--repetitive")
    return out


def main() -> int:
    a = parse_args()

    if a.gateway:
        # The gateway parent never touches jax — replicas are
        # subprocesses, the parent only routes and measures.
        return print(json.dumps(
            run_gateway_leg(a, passthrough_args(a))
        )) or 0

    if a.serve_worker:
        return serve_worker(a)

    if a.gang_worker:
        return gang_worker(a)

    if a.disagg:
        print(json.dumps(run_disagg_leg(a)))
        return 0

    if a.spec_overlap:
        print(json.dumps(run_spec_leg(a)))
        return 0

    if a.overlap:
        print(json.dumps(run_overlap_leg(a)))
        return 0

    if a.prefix_reuse:
        print(json.dumps(run_prefix_reuse_leg(a)))
        return 0

    if a.batchgen:
        print(json.dumps(run_batchgen_leg(a)))
        return 0

    if a.adapters:
        # Packed mixed-adapter engine vs base-only engine, same shape,
        # same process (ISSUE 6 acceptance: packed within 15% of base
        # with the simulated device step).
        import copy

        packed = measure(a)
        base_a = copy.copy(a)
        base_a.adapters = 0
        base = measure(base_a)
        ttft_packed = packed["ttft_ms"].get("p50")
        ttft_base = base["ttft_ms"].get("p50")
        record = {
            "metric": (
                f"{a.config.replace('-', '_')}_adapter_packed_throughput"
            ),
            "value": packed["gen_tok_s"],
            "unit": "gen_tokens/sec",
            "adapters": a.adapters,
            "base_value": base["gen_tok_s"],
            "packed_vs_base": (
                round(packed["gen_tok_s"] / base["gen_tok_s"], 3)
                if base["gen_tok_s"] else None
            ),
            "ttft_p50_ms": ttft_packed,
            "ttft_p50_ms_base": ttft_base,
            "ttft_delta_ms": (
                round(ttft_packed - ttft_base, 3)
                if ttft_packed is not None and ttft_base is not None
                else None
            ),
            "requests": a.requests,
            "max_tokens": a.max_tokens,
            "step_floor_ms": a.step_floor_ms,
            "quantize": a.quantize,
            "kv_layout": a.kv_layout,
            "wall_s": packed["wall_s"],
            "wall_s_base": base["wall_s"],
        }
        print(json.dumps(record))
        return 0

    if a.gang:
        base = passthrough_args(a)
        leader = run_gang(a, base)
        single = run_single_same_shape(a, base)
        ttft_gang = leader["ttft_ms"].get("p50")
        ttft_single = single["ttft_ms"].get("p50")
        record = {
            "metric": f"{a.config.replace('-', '_')}_engine_gang_throughput",
            "value": leader["gen_tok_s"],
            "unit": "gen_tokens/sec",
            "nprocs": a.gang,
            "devs_per_proc": a.devs_per_proc,
            "transport": a.transport,
            "single_value": single["gen_tok_s"],
            "gang_vs_single": (
                round(leader["gen_tok_s"] / single["gen_tok_s"], 3)
                if single["gen_tok_s"] else None
            ),
            "ttft_p50_ms": ttft_gang,
            "ttft_p50_ms_single": ttft_single,
            "ttft_delta_ms": (
                round(ttft_gang - ttft_single, 3)
                if ttft_gang is not None and ttft_single is not None
                else None
            ),
            "broadcast_ms": leader.get("broadcast_ms", {}),
            "admission": leader.get("admission"),
            "requests": a.requests,
            "quantize": a.quantize,
            "kv_layout": a.kv_layout,
            "wall_s": leader["wall_s"],
        }
        print(json.dumps(record))
        return 0

    mesh = None
    if a.mesh:
        from substratus_tpu.parallel.mesh import build_mesh

        axes = dict(
            (k, int(v))
            for k, v in (kv.split("=") for kv in a.mesh.split(","))
        )
        mesh = build_mesh(**axes)
    result = measure(a, mesh=mesh)
    if a.json_only:
        print(json.dumps(result))
        return 0
    record = {
        "metric": f"{a.config.replace('-', '_')}_engine_throughput",
        "value": result["gen_tok_s"],
        "unit": "gen_tokens/sec",
        "total_tok_s": result["total_tok_s"],
        "quantize": a.quantize,
        "kv_layout": a.kv_layout,
        "requests": a.requests,
        "wall_s": result["wall_s"],
        "ttft_p50_ms": result["ttft_ms"].get("p50"),
    }
    if result.get("spec"):
        record.update(
            spec_k=result["spec"]["spec_k"],
            acceptance=result["spec"]["acceptance"],
            verify_passes=result["spec"]["verify_passes"],
        )
    if result.get("admission"):
        record["admission"] = result["admission"]
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
