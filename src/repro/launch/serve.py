"""Serving driver: two workloads behind one CLI.

``decode``   — batched LLM prefill + decode loop with KV/SSM caches:

    python -m repro.launch.serve --workload decode --arch qwen2-1.5b \
        --batch 4 --prompt-len 32 --gen 16

``smoother`` — batched state-estimation service (DESIGN.md §Serving): a
fleet of smoothing requests with heterogeneous trajectory lengths is
bucketed by (padded n, nx), padded along time with uninformative
measurements (R inflated by ``R_PAD_SCALE`` so padded steps carry no
information) and along batch by replication, then each bucket runs as ONE
batched iterated smoother call — B trajectories per fused scan level.

Two serving modes:

* ``--arrival none`` (default) — the PR 2 one-shot path: all requests
  are present up front, buckets launch back-to-back (``--policy static``
  semantics, kept as the offline/batch entry point);
* ``--arrival poisson|bursty`` — a timestamped request stream driven
  through the autobatching queue (`launch/autobatch.py`):
  ``--policy deadline`` flushes buckets under per-request latency
  deadlines, ``--policy static`` is the fill-only baseline.

    python -m repro.launch.serve --workload smoother --requests 64 \
        --n 512 --max-batch 64 --tol 1e-6 \
        --arrival bursty --policy deadline --rate 8 --deadline 2.0

``--tenants`` makes the smoother workload multi-tenant (DESIGN.md §7):
each tenant is a scenario from the registry (`repro.scenarios`), served
by a `SmootherServer` built from the scenario's `SmootherSpec`
(`repro.core.build_smoother`) with an SLO class; one shared autobatching
queue routes mixed-scenario traffic by the ``spec_id``-keyed bucket
signature (`autobatch.spec_signature`), and the summary breaks
latency/deadline-hit down per tenant:

    python -m repro.launch.serve --workload smoother \
        --tenants coordinated_turn,bearings_only,pendulum:gold \
        --arrival bursty --policy deadline --requests 48 --n 64
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.autobatch import (SLO_CLASSES, VERDICT_DIVERGED,
                                    VERDICT_FAILED, VERDICT_OK,
                                    VERDICT_RETRIED, ComputeEstimator,
                                    FlushPolicy, QueuedRequest,
                                    make_arrivals, pad_width, run_service,
                                    spec_signature, summarize_service)
from repro.launch.chaos import ChaosConfig, ChaosInjector, \
    TransientComputeError
from repro.runtime import StepWatchdog, with_retries


# ---------------------------------------------------------------------------
# Decode workload (LLM serving)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServeConfig:
    arch: str
    batch: int = 4
    prompt_len: int = 32
    gen: int = 16
    max_len: int = 128
    reduced: bool = True
    seed: int = 0
    greedy: bool = True
    temperature: float = 1.0


def serve(serve_cfg: ServeConfig, emit=print) -> dict:
    from repro.configs import get_config, reduced_config
    from repro.models import decode_step, encode, init_caches, init_model

    cfg = get_config(serve_cfg.arch)
    if serve_cfg.reduced:
        cfg = reduced_config(cfg)
    params, _ = init_model(cfg, jax.random.PRNGKey(serve_cfg.seed))
    B = serve_cfg.batch
    key = jax.random.PRNGKey(serve_cfg.seed + 1)
    prompts = jax.random.randint(key, (B, serve_cfg.prompt_len), 0,
                                 cfg.vocab_size)
    memory = None
    if cfg.encoder_layers:
        memory = encode(params, cfg, jnp.zeros(
            (B, cfg.encoder_seq_len, cfg.d_model), jnp.float32))

    caches = init_caches(cfg, B, serve_cfg.max_len)

    @jax.jit
    def dstep(caches, tok, pos):
        return decode_step(params, cfg, caches, tok, pos, memory=memory)

    # Prompt processing via teacher-forced decode (exercises the cache
    # path end-to-end; a production server would use the prefill graph).
    t0 = time.perf_counter()
    logits = None
    for i in range(serve_cfg.prompt_len):
        logits, caches = dstep(caches, prompts[:, i:i + 1],
                               jnp.asarray(i, jnp.int32))
    generated = []
    tok = jnp.argmax(logits[:, :, :cfg.vocab_size], axis=-1) \
        .astype(jnp.int32)
    for j in range(serve_cfg.gen):
        generated.append(tok)
        logits, caches = dstep(
            caches, tok, jnp.asarray(serve_cfg.prompt_len + j, jnp.int32))
        tok = jnp.argmax(logits[:, :, :cfg.vocab_size], axis=-1) \
            .astype(jnp.int32)
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    out_tokens = jnp.concatenate(generated, axis=1)
    total = serve_cfg.prompt_len + serve_cfg.gen
    emit(f"[serve] {B} seqs x {total} steps in {dt:.2f}s "
         f"({B * total / dt:.1f} tok/s)")
    return {"tokens": out_tokens, "tok_per_s": B * total / dt}


# ---------------------------------------------------------------------------
# Smoother workload (batched state-estimation service)
# ---------------------------------------------------------------------------

R_PAD_SCALE = 1e8  # measurement-noise inflation on padded time steps

#: Work counters of the smoother launches (DESIGN.md §6): every
#: `SmootherServer.smooth_batch` adds its launch's counts to the server's
#: ``counters``, and each serving call reports what its own launches added.
#:   step_lanes_launched  b_pad x n_pad
#:   step_lanes_real      the real tracks' lengths
#:   lane_passes_run      real lanes x the loop's trip count (the most
#:                        passes any lane of the launch ran)
#:   lane_passes_active   real lanes' own passes (`LaneStatus.iterations`)
LAUNCH_COUNTERS = ("step_lanes_launched", "step_lanes_real",
                   "lane_passes_run", "lane_passes_active")


def launch_counts(n_pad: int, b_pad: int, lengths: List[int],
                  iterations: np.ndarray) -> Dict[str, int]:
    """One launch's `LAUNCH_COUNTERS` from its real tracks' lengths and
    its lanes' ``iterations`` (all ``b_pad``; the one copy of them to the
    host is made here)."""
    iterations = np.asarray(iterations)
    real = len(lengths)
    return {"step_lanes_launched": b_pad * n_pad,
            "step_lanes_real": int(sum(lengths)),
            "lane_passes_run": real * int(iterations.max()),
            "lane_passes_active": int(iterations[:real].sum())}


def _counts_since(servers, before: List[Dict[str, int]]) -> Dict[str, int]:
    """What ``servers``' launches added to their counters since the
    snapshots ``before`` (one per server, same order)."""
    return {k: sum(s.counters[k] - b[k] for s, b in zip(servers, before))
            for k in LAUNCH_COUNTERS}


def _backend_choices() -> Dict[str, str]:
    """The autotuner's measured combine-backend verdicts so far, keyed
    ``spec_id@platform/B=../T=../nx=..`` — surfaced in service stats so
    operators can see which buckets run the compiled kernel vs the fused
    twin (DESIGN.md §12)."""
    from repro.kernels.kalman_combine import autotune as kc_autotune

    return {k: v["choice"] for k, v in kc_autotune.cache_entries().items()}


@dataclasses.dataclass
class SmootherServeConfig:
    requests: int = 64
    n: int = 512             # maximum trajectory length in the request mix
    max_batch: int = 64      # bucket launch width
    method: str = "ekf"      # "ekf" | "slr"
    n_iter: int = 10
    tol: float = 1e-6        # 0 disables early stopping
    parallel: bool = True
    lm_lambda: float = 1.0   # damping; undamped GN diverges on long tracks
    vary_lengths: bool = True
    seed: int = 0
    f64: bool = True         # covariance form is f32-fragile at long n
    # Streaming mode (autobatch queue; "none" = one-shot PR 2 path).
    arrival: str = "none"    # "none" | "poisson" | "bursty"
    policy: str = "static"   # "static" | "deadline"
    rate: float = 8.0        # offered load, requests/s (simulated clock)
    burst_size: int = 8      # bursty: requests per burst
    deadline_s: float = 2.0  # per-request completion budget
    max_wait_s: float = 0.25  # queue-wait cap (starvation bound)
    slack: float = 1.25      # safety factor on predicted compute
    warm: bool = True        # pre-compile bucket signatures before serving
    # Fault injection (streaming mode only; see launch/chaos.py).
    chaos_rate: float = 0.0  # headline rate for ChaosConfig.at_rate
    chaos_seed: int = 0

    def chaos_config(self) -> Optional["ChaosConfig"]:
        """The `ChaosConfig` for ``chaos_rate`` (None when disabled)."""
        if self.chaos_rate <= 0:
            return None
        return ChaosConfig.at_rate(self.chaos_rate, seed=self.chaos_seed)


def serve_dtype(cfg: SmootherServeConfig):
    """The dtype the service runs in, from ``cfg.f64``.

    float64 does not run on the TPU: XLA:TPU implements its LU
    decomposition (the smoother's linear solves) in float32 only, so the
    bucket executable fails to compile. Refuse it here, with a clear
    message, instead of failing inside the first launch."""
    if not cfg.f64:
        return jnp.float32
    if jax.default_backend() == "tpu":
        raise ValueError(
            "float64 serving does not run on the TPU (XLA:TPU has no "
            "float64 LU decomposition); serve in float32: --f32 on the "
            "CLI, SmootherServeConfig(f64=False) in code")
    jax.config.update("jax_enable_x64", True)
    return jnp.float64


def pad_requests(batch: List[np.ndarray], n_pad: int, b_pad: int,
                 R: np.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pad a bucket of measurement sequences to ``[b_pad, n_pad, ny]``.

    Time padding appends zero measurements whose per-step R is inflated
    by ``R_PAD_SCALE`` (an exactly-uninformative update up to float
    error — the serving contract pinned by
    tests/core/test_batched_parity.py); batch padding replicates lane 0.
    Returns the padded measurements and the per-lane, per-step R stack.
    """
    R = np.asarray(R)
    ny = R.shape[-1]
    ys = np.zeros((b_pad, n_pad, ny), R.dtype)
    rs = np.broadcast_to(R * R_PAD_SCALE, (b_pad, n_pad, ny, ny)).copy()
    for i, y in enumerate(batch):
        ys[i, :len(y)] = y
        rs[i, :len(y)] = R
    for i in range(len(batch), b_pad):           # batch padding: replicate
        ys[i] = ys[0]
        rs[i] = rs[0]
    return jnp.asarray(ys), jnp.asarray(rs)


class SmootherServer:
    """Bucketed batched smoothing service over one state-space model.

    Requests (``ys [n_i, ny]``) are grouped by the shared
    `autobatch.spec_signature` key ``(spec_id, method, next_pow2(n_i),
    nx)``; inside a bucket the time axis is padded to the bucket length
    with zero measurements whose per-step R is inflated by
    ``R_PAD_SCALE`` (an exactly-uninformative update up to float error,
    so real-step posteriors are unchanged), and the batch axis is padded
    by replication to the launch width. Each (B, n) signature jit-caches
    one batched iterated-smoother executable.

    The smoother configuration is a `repro.core.SmootherSpec` —
    ``spec`` pins it directly (a registry tenant passes
    ``scenario.default_spec(...)``, which carries the scenario
    ``model_id`` into ``spec_id``); ``icfg`` lifts a legacy
    `IteratedConfig` onto the spec axes; with neither, the spec is built
    from the `SmootherServeConfig` knobs. Either way the executable is
    `repro.core.build_smoother`'s and every cache key carries the full
    spec identity (``IteratedConfig.model_id == spec.spec_id``).
    """

    def __init__(self, model, cfg: SmootherServeConfig, icfg=None,
                 tenant: str = "", spec=None):
        from repro.core import SmootherSpec, build_smoother

        self.model = model
        self.cfg = cfg
        self.tenant = tenant
        if spec is None:
            if icfg is not None:
                spec = SmootherSpec.from_iterated_config(icfg)
            else:
                spec = SmootherSpec(
                    mode="parallel" if cfg.parallel else "sequential",
                    linearization=("taylor" if cfg.method == "ekf"
                                   else "slr"),
                    n_iter=cfg.n_iter, tol=cfg.tol,
                    lm_lambda=cfg.lm_lambda)
        self.spec = spec
        self._smoother = build_smoother(spec)
        self._icfg = self._smoother.config   # model_id == spec.spec_id
        self._run = self._make_run(self._smoother)
        # The bounded-retry lane (DESIGN.md §13): same spec with adaptive
        # per-lane LM damping and a stronger initial lambda. Requests
        # whose primary lane diverges are re-enqueued once here; the
        # distinct spec_id routes them to their own buckets, so retry
        # traffic never perturbs healthy buckets' composition.
        retry_spec = dataclasses.replace(
            spec, damping="adaptive",
            lm_lambda=max(spec.lm_lambda * 10.0, 10.0))
        self._retry_smoother = build_smoother(retry_spec)
        self._retry_run = self._make_run(self._retry_smoother)
        # Second-failure fallback: the sequential adaptive smoother, run
        # per trajectory (no parallel-scan conditioning, most robust
        # pass we have). Square-root factors only exist for the parallel
        # combines, so the form drops to standard covariance here.
        fallback_spec = dataclasses.replace(
            retry_spec, mode="sequential", form="standard")
        self._fallback_smoother = build_smoother(fallback_spec)
        self._fallback_run = self._make_run(self._fallback_smoother)
        # Per-bucket executable signatures seen so far (compile-count
        # bookkeeping; jax.jit caches by shape, this mirrors its keys).
        self.signatures_seen = set()
        # Work counters summed over every launch of this server.
        self.counters = dict.fromkeys(LAUNCH_COUNTERS, 0)

    def _make_run(self, smoother):
        def run(ys, r_stack):
            model_b = dataclasses.replace(self.model, R=r_stack)
            traj, info = smoother.iterate(model_b, ys, return_info=True)
            # Per-step fit scores; padded steps are masked host-side
            # (their inflated-R terms belong to no request).
            ll_steps = smoother.log_likelihood(model_b, ys, traj,
                                               per_step=True)
            return traj, info, ll_steps

        return jax.jit(run)

    @property
    def icfg(self):
        return self._icfg

    @property
    def model_id(self) -> str:
        """The server's routing identity: the spec's content hash (rides
        in the legacy ``model_id`` slot of queue requests and cache
        keys)."""
        return self._icfg.model_id

    @property
    def retry_model_id(self) -> str:
        """Routing identity of the bounded-retry lane (adaptive-damping
        spec); requests re-enqueued after a lane failure carry this id
        so the queue buckets them separately from healthy traffic."""
        return self._retry_smoother.config.model_id

    def queue_signature(self, n: int):
        """The autobatch bucket key for a request of length ``n`` against
        this server's spec — the single shared key-construction path
        (DESIGN.md §7), now derived from ``spec_id``."""
        return spec_signature(self.spec, n, self.model.nx)

    def _pad_bucket(self, batch: List[np.ndarray], n_pad: int, b_pad: int
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return pad_requests(batch, n_pad, b_pad, np.asarray(self.model.R))

    def smooth_batch(self, batch: List[np.ndarray], n_pad: int, b_pad: int,
                     lane: str = "primary"):
        """Run one padded bucket launch; returns per-request trajectories
        (list of ``[n_i + 1, nx]`` means), the per-lane iteration info,
        per-request smoothed log-likelihood fit scores (real steps only —
        padded-step terms are masked out), and per-request lane health
        (True = finite posterior and not `LANE_DIVERGED`).

        ``lane`` selects the executable: ``"primary"`` is the server's
        spec, ``"retry"`` the adaptive-damping bounded-retry spec.

        The launch is one ``serve.launch`` profiler span (arguments
        ``n_pad``, ``b_pad``, ``lanes``: the real ones, ``lane``) around
        four: ``serve.pad`` (padding and the copy to the device),
        ``serve.dispatch`` (the jitted call), ``serve.wait`` (until the
        device is done) and ``serve.unpack`` (answers to the host, lane
        health, `LAUNCH_COUNTERS`)."""
        from repro.core import LANE_DIVERGED

        span = jax.profiler.TraceAnnotation
        smoother_run, icfg = ((self._run, self._icfg)
                              if lane == "primary"
                              else (self._retry_run,
                                    self._retry_smoother.config))
        self.signatures_seen.add(
            icfg.cache_key(n_pad, b_pad, self.model.nx))
        with span("serve.launch", n_pad=n_pad, b_pad=b_pad,
                  lanes=len(batch), lane=lane):
            with span("serve.pad"):
                ys, rs = self._pad_bucket(batch, n_pad, b_pad)
            with span("serve.dispatch"):
                traj, info, ll_steps = smoother_run(ys, rs)
            with span("serve.wait"):
                jax.block_until_ready(traj.mean)
            with span("serve.unpack"):
                means = [np.asarray(traj.mean[i, :len(y) + 1])
                         for i, y in enumerate(batch)]
                ll_steps = np.asarray(ll_steps)
                logliks = [float(np.sum(ll_steps[i, :len(y)]))
                           for i, y in enumerate(batch)]
                codes = np.asarray(info.code)
                health = [bool(codes[i] != LANE_DIVERGED)
                          and bool(np.isfinite(m).all())
                          for i, m in enumerate(means)]
                for k, v in launch_counts(n_pad, b_pad,
                                          [len(y) for y in batch],
                                          info.iterations).items():
                    self.counters[k] += v
        return means, info, logliks, health

    def warmup(self, n_pads, b_pads, estimator: ComputeEstimator = None):
        """Pre-compile every (n_pad, b_pad) bucket signature and, when an
        estimator is given, seed it with a warm measured launch each.

        Compile time must not pollute streaming latency (a production
        server warms its executables at deploy time); the warm call is
        what the deadline policy should budget for. Signatures already
        seen skip the compile call, and without an estimator (static
        policy never consults one) nothing warm is re-measured — so a
        shared server pays for each signature once, not per stream.
        """
        ny = self.model.ny
        for n_pad in sorted(set(n_pads)):
            dummy = [np.zeros((n_pad, ny))]
            for b_pad in sorted(set(b_pads)):
                # backend="auto": measure kernel-vs-fused for this bucket
                # shape *before* the executable traces, so the trace bakes
                # in the measured winner (idempotent per (spec_id, shape);
                # on hosts with no compiled lowering it records "fused"
                # without timing anything).
                if self.spec.backend == "auto":
                    with jax.profiler.TraceAnnotation("serve.autotune"):
                        self._smoother.autotune(b_pad, n_pad, self.model.nx,
                                                self.model.m0.dtype)
                key = self._icfg.cache_key(n_pad, b_pad, self.model.nx)
                if key not in self.signatures_seen:
                    self.smooth_batch(dummy, n_pad, b_pad)  # compile
                if estimator is not None:
                    t0 = time.perf_counter()
                    _, info, _, _ = self.smooth_batch(dummy, n_pad, b_pad)
                    dt = time.perf_counter() - t0
                    # The zero-measurement dummy converges early under
                    # tol>0; scale to the full pass budget so the seed
                    # upper-bounds real traffic (a low seed would make
                    # the deadline trigger fire too late until the EMA
                    # catches up).
                    iters = float(np.mean(np.asarray(info.iterations)))
                    if self._icfg.tol > 0.0 and iters >= 1.0:
                        dt *= self._icfg.n_iter / iters
                    # warmed: this is a post-compile timing — it may seed
                    # the EMA directly (the estimator discards unmarked
                    # first observations as compile-poisoned).
                    estimator.observe(self.queue_signature(n_pad), b_pad,
                                      dt, warmed=True)

    def warmup_retry(self, n_pads):
        """Pre-compile the bounded-retry and fallback executables for the
        given bucket lengths (narrow widths only — retry buckets hold the
        rare failed requests, not full batches). Chaos runs warm these up
        front so injected faults measure the retry *policy*, not compile
        time; unwarmed widths still work, they just compile on first
        use."""
        ny = self.model.ny
        for n_pad in sorted(set(n_pads)):
            dummy = np.zeros((n_pad, ny))
            for b_pad in (1, 2):
                self.smooth_batch([dummy], n_pad, b_pad, lane="retry")
            self._fallback_single(dummy, n_pad)

    def retry_request(self, req: QueuedRequest) -> QueuedRequest:
        """The re-enqueue hook handed to `autobatch.run_service`: rewrite
        a failed request onto the bounded-retry lane (adaptive damping),
        bumping ``attempt``. Arrival and deadline are preserved — a retry
        does not buy the request more SLO budget."""
        return dataclasses.replace(req, model_id=self.retry_model_id,
                                   attempt=req.attempt + 1)

    def _fallback_single(self, ys: np.ndarray, n_pad: int):
        """Sequential adaptive smoothing of ONE trajectory — the
        last-resort pass after the batched retry lane also failed.
        Returns ``(mean, loglik, healthy)``; a still-diverged lane comes
        back frozen at its last finite iterate with ``healthy=False``."""
        from repro.core import LANE_DIVERGED

        ys = np.asarray(ys)
        ys_p, rs = self._pad_bucket([ys], n_pad, 1)
        traj, info, ll_steps = self._fallback_run(ys_p, rs)
        jax.block_until_ready(traj.mean)
        mean = np.asarray(traj.mean[0, :len(ys) + 1])
        ll = float(np.sum(np.asarray(ll_steps)[0, :len(ys)]))
        code = int(np.asarray(info.code).reshape(-1)[0])
        healthy = (code != LANE_DIVERGED) and bool(np.isfinite(mean).all())
        return mean, ll, healthy

    def run_flush(self, fl):
        """Execute one queue flush with lane-health classification.

        Routes the flush to the primary or retry executable by its
        signature, classifies every request by its lane's `LaneStatus`,
        and — for requests already on the retry lane that fail again —
        runs the sequential per-trajectory fallback inline. Returns
        ``(dt, outcomes, store, iters)``: measured wall seconds, the
        per-request verdict dict `run_service` consumes, the results to
        publish (``req_id -> (mean, loglik)``; a failed attempt-0 entry
        holds the diverged lane's output and is overwritten when its
        retry completes), and total iterations spent.
        """
        lane = ("retry" if fl.signature[0] == self.retry_model_id
                else "primary")
        batch = [r.payload for r in fl.requests]
        n_pad = fl.signature[2]
        t0 = time.perf_counter()
        means, info, lls, health = self.smooth_batch(
            batch, n_pad, fl.b_pad, lane=lane)
        outcomes, store = {}, {}
        for i, r in enumerate(fl.requests):
            if health[i]:
                outcomes[r.req_id] = (VERDICT_OK if r.attempt == 0
                                      else VERDICT_RETRIED)
                store[r.req_id] = (means[i], lls[i])
            elif r.attempt == 0:
                # Withhold the diverged posterior; run_service re-enqueues
                # through retry_request (or degrades to DIVERGED if no
                # retry hook is installed — publish the frozen iterate).
                outcomes[r.req_id] = VERDICT_FAILED
                store[r.req_id] = (means[i], lls[i])
            else:
                m, ll, ok = self._fallback_single(r.payload, n_pad)
                outcomes[r.req_id] = (VERDICT_RETRIED if ok
                                      else VERDICT_DIVERGED)
                store[r.req_id] = (m, ll)
        dt = time.perf_counter() - t0
        iters = int(np.sum(np.asarray(info.iterations)[:len(batch)]))
        return dt, outcomes, store, iters

    def serve_requests(self, requests: List[np.ndarray], emit=print) -> dict:
        """Bucket, pad, and smooth a full request list; returns stats."""
        buckets: Dict[tuple, List[int]] = defaultdict(list)
        for idx, ys in enumerate(requests):
            # The shared bucket key (autobatch.spec_signature): the
            # one-shot path and the streaming queue cannot drift.
            buckets[self.queue_signature(len(ys))].append(idx)

        results: List[Optional[np.ndarray]] = [None] * len(requests)
        logliks: List[Optional[float]] = [None] * len(requests)
        verdicts: Dict[str, int] = defaultdict(int)
        launches = 0
        before = [dict(self.counters)]
        t0 = time.perf_counter()
        for sig in sorted(buckets):
            n_pad = sig[2]
            idxs = buckets[sig]
            for lo in range(0, len(idxs), self.cfg.max_batch):
                chunk = idxs[lo:lo + self.cfg.max_batch]
                # Same pow2 width quantization as the streaming path
                # (autobatch.pad_width): one bounded executable-cache
                # contract whether requests arrive one-shot or queued.
                b_pad = pad_width(len(chunk), self.cfg.max_batch)
                means, _, lls, health = self.smooth_batch(
                    [requests[i] for i in chunk], n_pad, b_pad)
                for i, m, ll, ok in zip(chunk, means, lls, health):
                    results[i] = m
                    logliks[i] = ll
                    # No retry lane here: a diverged lane is reported.
                    verdicts[VERDICT_OK if ok else VERDICT_DIVERGED] += 1
                launches += 1
        dt = time.perf_counter() - t0
        counts = _counts_since([self], before)
        stats = {
            "results": results,
            "logliks": logliks,
            "requests": len(requests),
            "launches": launches,
            "verdicts": dict(verdicts),
            "compiles": len(self.signatures_seen),
            "mean_iterations": (counts["lane_passes_active"]
                                / max(len(requests), 1)),
            "wall_s": dt,
            "traj_per_s": len(requests) / dt,
            **counts,
        }
        emit(f"[serve/smoother] {len(requests)} requests in {launches} "
             f"bucket launches, {dt:.2f}s ({stats['traj_per_s']:.1f} traj/s,"
             f" {stats['mean_iterations']:.1f} mean iters)")
        return stats

    def serve_stream(self, requests: List[np.ndarray],
                     arrivals: np.ndarray, emit=print,
                     policy: Optional[FlushPolicy] = None,
                     chaos: Optional[ChaosConfig] = None) -> dict:
        """Serve a *timestamped* request stream through the autobatching
        queue (simulated arrival clock, measured bucket compute).

        Flush knobs default to the server config (``policy`` selects
        deadline-aware vs fill-only flushing, ``deadline_s`` /
        ``max_wait_s`` / ``slack`` bound per-request latency); pass an
        explicit `FlushPolicy` to sweep policies on one warm server —
        the *smoother* config (method/n_iter/tol/...) is baked into the
        jitted executable at construction and is deliberately not
        re-read here. Returns the per-request results plus the latency
        digest of `autobatch.summarize_service`.

        ``chaos`` injects the seeded fault mix of `launch.chaos` into
        the stream: corrupted payloads go through the full
        retry/fallback pipeline, transient executor exceptions are
        absorbed in place by `with_retries`, and injected stragglers are
        flagged by the `StepWatchdog` without polluting the compute EMA.
        """
        cfg = self.cfg
        if policy is None:
            policy = FlushPolicy(kind=cfg.policy, max_batch=cfg.max_batch,
                                 max_wait=cfg.max_wait_s, slack=cfg.slack)
        estimator = ComputeEstimator(policy.ema_alpha,
                                     policy.default_compute)
        injector = None
        if chaos is not None and chaos.active:
            injector = ChaosInjector(chaos)
            requests, _ = injector.corrupt_requests(requests)
        qreqs = [QueuedRequest(req_id=i, n=len(ys), nx=self.model.nx,
                               arrival=float(t),
                               deadline=float(t) + cfg.deadline_s,
                               payload=ys, model_id=self.model_id,
                               method=self._icfg.method,
                               tenant=self.tenant)
                 for i, (ys, t) in enumerate(zip(requests, arrivals))]
        if cfg.warm:
            n_pads = {r.signature[2] for r in qreqs}
            b_pads = {policy.pad_width(k)
                      for k in range(1, cfg.max_batch + 1)}
            self.warmup(n_pads, b_pads,
                        estimator if policy.kind == "deadline" else None)
            if injector is not None:
                self.warmup_retry(n_pads)

        results: List[Optional[np.ndarray]] = [None] * len(requests)
        logliks: List[Optional[float]] = [None] * len(requests)
        before = [dict(self.counters)]

        def execute(fl):
            dt, outcomes, store, _ = self.run_flush(fl)
            for rid, (m, ll) in store.items():
                results[rid] = m
                logliks[rid] = ll
            return dt, outcomes

        exec_fn = execute
        if injector is not None:
            exec_fn = with_retries(injector.wrap_execute(execute),
                                   max_retries=1,
                                   retry_on=(TransientComputeError,))
        service = run_service(qreqs, exec_fn, policy, estimator,
                              retry=self.retry_request,
                              watchdog=StepWatchdog())
        counts = _counts_since([self], before)
        stats = summarize_service(service)
        stats.update({
            "results": results,
            "logliks": logliks,
            "mean_iterations": (counts["lane_passes_active"]
                                / max(len(requests), 1)),
            "compiles": len(self.signatures_seen),
            "records": service["records"],
            "launch_log": service["launches"],
            "backend_choices": _backend_choices(),
            "chaos": (injector.summary() if injector is not None
                      else None),
            **counts,
        })
        emit(f"[serve/smoother/{policy.kind}] {stats['requests']} requests "
             f"in {stats['launches']} launches "
             f"(p50 {stats['latency_p50_s'] * 1e3:.1f}ms, "
             f"p95 {stats['latency_p95_s'] * 1e3:.1f}ms, "
             f"{stats['traj_per_s']:.1f} traj/s, "
             f"deadline hit {stats['deadline_hit_rate']:.0%}, "
             f"occupancy {stats['occupancy']:.2f})")
        if injector is not None:
            emit(f"[serve/chaos] injected {stats['chaos']['fault_kinds']}"
                 f" + {stats['chaos']['exceptions']} transient exceptions"
                 f" + {stats['chaos']['stragglers']} stragglers -> "
                 f"verdicts {stats['verdicts']}")
        return stats


# ---------------------------------------------------------------------------
# Multi-tenant serving (scenario registry tenants; DESIGN.md §7)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """One tenant of the multi-tenant smoother service: a registry
    scenario plus its SLO class. ``deadline_s=None`` takes the class
    default (`autobatch.SLO_CLASSES`); ``weight`` is the tenant's share
    of the generated request mix."""

    tenant: str
    scenario: str
    slo: str = "standard"
    weight: float = 1.0
    deadline_s: Optional[float] = None

    def __post_init__(self):
        if self.slo not in SLO_CLASSES:
            raise ValueError(f"unknown SLO class {self.slo!r}; "
                             f"available: {sorted(SLO_CLASSES)}")

    @classmethod
    def parse(cls, spec: str) -> "TenantSpec":
        """CLI syntax: ``scenario[:slo[:weight]]`` (e.g.
        ``pendulum:gold`` or ``lorenz96:batch:0.5``); empty fields take
        the defaults."""
        parts = spec.split(":")
        name = parts[0]
        slo = parts[1] if len(parts) > 1 and parts[1] else "standard"
        try:
            weight = (float(parts[2])
                      if len(parts) > 2 and parts[2] else 1.0)
        except ValueError as e:
            raise ValueError(
                f"bad tenant spec {spec!r}: weight must be a float "
                f"(syntax: scenario[:slo[:weight]])") from e
        return cls(tenant=name, scenario=name, slo=slo, weight=weight)

    @property
    def slo_class(self):
        return SLO_CLASSES[self.slo]

    @property
    def budget_s(self) -> float:
        return (self.deadline_s if self.deadline_s is not None
                else self.slo_class.deadline_s)

    def smoother_spec(self, cfg: "SmootherServeConfig"):
        """The tenant's `repro.core.SmootherSpec`: the registry
        scenario's production defaults (linearization family, sigma
        scheme, damping, ``model_id``) plus the service-level iteration
        knobs — the declarative contract its `SmootherServer` is built
        from."""
        from repro.scenarios import get_scenario

        return get_scenario(self.scenario).default_spec(
            n_iter=cfg.n_iter, tol=cfg.tol,
            mode="parallel" if cfg.parallel else "sequential")


class MultiTenantServer:
    """One autobatching queue over several scenario models.

    Each tenant owns a `SmootherServer` built from its registry
    scenario's default smoother configuration (linearization method,
    sigma scheme, damping, ``model_id``); the queue's bucket signature
    ``(model_id, method, n_pad, nx)`` routes every flush back to the
    owning tenant, so batches never mix models (the executable is
    per-model anyway — mixing would be mathematically wrong, not just
    slow). Deadlines and launch priority come from the tenant's SLO
    class; `summarize_service` reports the per-tenant latency and
    deadline-hit breakdown.
    """

    def __init__(self, tenants: List[TenantSpec], cfg: SmootherServeConfig):
        from repro.scenarios import get_scenario

        if not tenants:
            raise ValueError("need at least one tenant")
        dtype = serve_dtype(cfg)
        self.cfg = cfg
        self.specs: Dict[str, TenantSpec] = {}
        self.servers: Dict[str, SmootherServer] = {}
        self._by_model: Dict[Tuple[str, str], SmootherServer] = {}
        for tspec in tenants:
            if tspec.tenant in self.specs:
                raise ValueError(f"duplicate tenant {tspec.tenant!r}")
            sc = get_scenario(tspec.scenario)
            sspec = tspec.smoother_spec(cfg)
            server = SmootherServer(sc.make_model(dtype), cfg, spec=sspec,
                                    tenant=tspec.tenant)
            self.specs[tspec.tenant] = tspec
            self.servers[tspec.tenant] = server
            route = (server.model_id, sspec.method)
            if route in self._by_model:
                raise ValueError(
                    f"tenants {tspec.tenant!r} and "
                    f"{self._by_model[route].tenant!r} resolve to the same "
                    f"(model_id, method) route — deduplicate them upstream")
            self._by_model[route] = server
            # Retry-lane route: re-enqueued requests carry the retry
            # spec_id and must flush back to the owning server. The
            # adaptive spec_id differs from every primary one, so this
            # can't collide with the duplicate check above.
            self._by_model[(server.retry_model_id, sspec.method)] = server

    def scenario_of(self, tenant: str):
        return self.specs[tenant]

    def retry_request(self, req: QueuedRequest) -> QueuedRequest:
        """Route a failed request onto its owning server's retry lane
        (the request still carries the primary ``model_id`` at attempt
        0, which is exactly the routing key)."""
        return self._by_model[(req.model_id, req.method)] \
            .retry_request(req)

    def serve_stream(self, requests: List[Tuple[str, np.ndarray]],
                     arrivals: np.ndarray, emit=print,
                     policy: Optional[FlushPolicy] = None,
                     chaos: Optional[ChaosConfig] = None) -> dict:
        """Serve a timestamped *mixed* stream of ``(tenant, ys)`` pairs.

        Per-tenant warmup pre-compiles each tenant's bucket signatures
        and seeds the shared compute estimator, so streaming latency
        never pays compile time regardless of which tenant a bucket
        belongs to. ``chaos`` injects the seeded fault mix of
        `launch.chaos` across the whole mixed stream (see
        `SmootherServer.serve_stream`).
        """
        cfg = self.cfg
        if policy is None:
            policy = FlushPolicy(kind=cfg.policy, max_batch=cfg.max_batch,
                                 max_wait=cfg.max_wait_s, slack=cfg.slack)
        estimator = ComputeEstimator(policy.ema_alpha,
                                     policy.default_compute)
        injector = None
        if chaos is not None and chaos.active:
            injector = ChaosInjector(chaos)
            requests, _ = injector.corrupt_requests(requests)
        qreqs = []
        for i, ((tenant, ys), t) in enumerate(zip(requests, arrivals)):
            spec = self.specs[tenant]
            server = self.servers[tenant]
            qreqs.append(QueuedRequest(
                req_id=i, n=len(ys), nx=server.model.nx, arrival=float(t),
                deadline=float(t) + spec.budget_s, payload=ys,
                model_id=server.model_id, method=server.icfg.method,
                tenant=tenant, priority=spec.slo_class.priority))
        if cfg.warm:
            b_pads = {policy.pad_width(k)
                      for k in range(1, cfg.max_batch + 1)}
            for tenant, server in self.servers.items():
                n_pads = {r.signature[2] for r in qreqs
                          if r.tenant == tenant}
                if n_pads:
                    server.warmup(
                        n_pads, b_pads,
                        estimator if policy.kind == "deadline" else None)
                    if injector is not None:
                        server.warmup_retry(n_pads)

        results: List[Optional[np.ndarray]] = [None] * len(requests)
        logliks: List[Optional[float]] = [None] * len(requests)
        servers = list(self.servers.values())
        before = [dict(s.counters) for s in servers]

        def execute(fl):
            model_id, method, _, _ = fl.signature
            server = self._by_model[(model_id, method)]
            dt, outcomes, store, _ = server.run_flush(fl)
            for rid, (m, ll) in store.items():
                results[rid] = m
                logliks[rid] = ll
            return dt, outcomes

        exec_fn = execute
        if injector is not None:
            exec_fn = with_retries(injector.wrap_execute(execute),
                                   max_retries=1,
                                   retry_on=(TransientComputeError,))
        service = run_service(qreqs, exec_fn, policy, estimator,
                              retry=self.retry_request,
                              watchdog=StepWatchdog())
        counts = _counts_since(servers, before)
        stats = summarize_service(service)
        stats.update({
            "results": results,
            "logliks": logliks,
            "mean_iterations": (counts["lane_passes_active"]
                                / max(len(requests), 1)),
            "compiles": sum(len(s.signatures_seen) for s in servers),
            "records": service["records"],
            "launch_log": service["launches"],
            "backend_choices": _backend_choices(),
            "chaos": (injector.summary() if injector is not None
                      else None),
            **counts,
        })
        emit(f"[serve/smoother/mt/{policy.kind}] {stats['requests']} "
             f"requests, {len(self.servers)} tenants, "
             f"{stats['launches']} launches "
             f"(p95 {stats['latency_p95_s'] * 1e3:.1f}ms, "
             f"deadline hit {stats['deadline_hit_rate']:.0%}, "
             f"occupancy {stats['occupancy']:.2f})")
        if injector is not None:
            emit(f"[serve/chaos] injected {stats['chaos']['fault_kinds']}"
                 f" + {stats['chaos']['exceptions']} transient exceptions"
                 f" + {stats['chaos']['stragglers']} stragglers -> "
                 f"verdicts {stats['verdicts']}")
        for tenant, digest in stats.get("per_tenant", {}).items():
            spec = self.specs[tenant]
            emit(f"  [tenant {tenant} ({spec.slo})] "
                 f"{digest['requests']} reqs, "
                 f"p50 {digest['latency_p50_s'] * 1e3:.1f}ms, "
                 f"p95 {digest['latency_p95_s'] * 1e3:.1f}ms, "
                 f"deadline hit {digest['deadline_hit_rate']:.0%}")
        return stats


def make_tenant_fleet(server: MultiTenantServer, n_requests: int, n: int,
                      vary_lengths: bool = True, seed: int = 0):
    """Generate a mixed-scenario request fleet for a multi-tenant server:
    per request, draw a tenant by ``TenantSpec.weight`` and a length
    from the same varied-length mix as the single-tenant driver.
    Returns ``(requests [(tenant, ys)], truths [xs])`` — the single
    generation path shared by `serve_smoother_multitenant` and
    `benchmarks/serve_bench.run_multitenant`."""
    from repro.scenarios import get_scenario

    names = list(server.specs)
    weights = np.asarray([server.specs[t].weight for t in names])
    weights = weights / weights.sum()
    lengths = ([max(n // 2, 2), max((3 * n) // 4, 2), n]
               if vary_lengths else [n])
    rng = np.random.default_rng(seed)
    requests, truths = [], []
    for i in range(n_requests):
        tenant = names[int(rng.choice(len(names), p=weights))]
        sc = get_scenario(server.specs[tenant].scenario)
        model = server.servers[tenant].model
        n_i = int(lengths[int(rng.integers(len(lengths)))])
        xs, ys = sc.simulate(model, n_i, jax.random.PRNGKey(seed + i))
        requests.append((tenant, np.asarray(ys)))
        truths.append(np.asarray(xs))
    return requests, truths


def serve_smoother_multitenant(cfg: SmootherServeConfig,
                               tenants: List[TenantSpec],
                               emit=print) -> dict:
    """Generate a mixed-scenario request fleet and serve it through one
    multi-tenant queue. Tenants are drawn by ``weight`` per request;
    lengths follow the same varied-length mix as the single-tenant
    driver. ``--arrival none`` degenerates to an all-at-t=0 stream."""
    server = MultiTenantServer(tenants, cfg)
    requests, truths = make_tenant_fleet(server, cfg.requests, cfg.n,
                                         cfg.vary_lengths, cfg.seed)

    if cfg.arrival == "none":
        arrivals = np.zeros(cfg.requests)
    else:
        arrivals = make_arrivals(cfg.arrival, cfg.requests, cfg.rate,
                                 cfg.burst_size, seed=cfg.seed)
    stats = server.serve_stream(requests, arrivals, emit=emit,
                                chaos=cfg.chaos_config())

    # Statistical sanity per tenant: full-state RMSE against the
    # simulated truth (position-only RMSE would be meaningless for the
    # scalar scenarios) and the mean smoothed log-likelihood fit score.
    # Under chaos, shed requests have no result and corrupted ones track
    # a corrupted truth — only healthy completions are scored.
    ll_by: Dict[str, List[float]] = defaultdict(list)
    rmse_by: Dict[str, List[float]] = defaultdict(list)
    healthy = {r["req_id"] for r in stats["records"]
               if r["verdict"] == VERDICT_OK}
    for i, ((tenant, _), ll, mean, xs) in enumerate(
            zip(requests, stats["logliks"], stats["results"], truths)):
        if i not in healthy or mean is None:
            continue
        ll_by[tenant].append(ll)
        rmse_by[tenant].append(
            float(np.sqrt(np.mean((mean[1:] - xs[1:]) ** 2))))
    stats["mean_loglik_per_tenant"] = {
        t: float(np.mean(v)) for t, v in sorted(ll_by.items())}
    stats["mean_rmse_per_tenant"] = {
        t: float(np.mean(v)) for t, v in sorted(rmse_by.items())}
    for t in stats["mean_loglik_per_tenant"]:
        emit(f"  [tenant {t}] mean state RMSE "
             f"{stats['mean_rmse_per_tenant'][t]:.4f}, "
             f"mean smoothed loglik "
             f"{stats['mean_loglik_per_tenant'][t]:.1f}")
    return stats


def make_fleet(sc, model, cfg: SmootherServeConfig):
    """The single-tenant request fleet of `serve_smoother`, from
    ``cfg.seed``: ``cfg.requests`` trajectories of scenario ``sc``.
    Returns ``(requests [ys], truths [xs])``."""
    # A small set of distinct lengths keeps request generation cheap while
    # still exercising the (n, nx) bucketing + padding path.
    lengths = ([max(cfg.n // 2, 2), max((3 * cfg.n) // 4, 2), cfg.n]
               if cfg.vary_lengths else [cfg.n])
    rng = np.random.default_rng(cfg.seed)
    requests, truths = [], []
    for i in range(cfg.requests):
        n_i = int(lengths[int(rng.integers(len(lengths)))])
        xs, ys = sc.simulate(model, n_i, jax.random.PRNGKey(cfg.seed + i))
        requests.append(np.asarray(ys))
        truths.append(np.asarray(xs))
    return requests, truths


def serve_smoother(cfg: SmootherServeConfig, emit=print) -> dict:
    """Generate a synthetic coordinated-turn request fleet and serve it."""
    from repro.scenarios import get_scenario

    dtype = serve_dtype(cfg)
    sc = get_scenario("coordinated_turn")
    model = sc.make_model(dtype)
    requests, truths = make_fleet(sc, model, cfg)

    # Single-tenant smoother knobs from SmootherServeConfig lifted onto
    # the scenario's spec (the registry model_id rides inside spec_id —
    # shared bucketing contract with the multi-tenant path).
    sspec = sc.default_spec(
        linearization="taylor" if cfg.method == "ekf" else "slr",
        mode="parallel" if cfg.parallel else "sequential",
        n_iter=cfg.n_iter, tol=cfg.tol, lm_lambda=cfg.lm_lambda)
    server = SmootherServer(model, cfg, spec=sspec, tenant=sc.name)
    if cfg.arrival == "none":
        stats = server.serve_requests(requests, emit=emit)
    else:
        arrivals = make_arrivals(cfg.arrival, cfg.requests, cfg.rate,
                                 cfg.burst_size, seed=cfg.seed)
        stats = server.serve_stream(requests, arrivals, emit=emit,
                                    chaos=cfg.chaos_config())

    # Sanity: served estimates must actually track the simulated truth.
    # Shed/corrupted requests are excluded — only "ok" completions (or
    # everything on the chaos-free one-shot path) are scored.
    healthy = {r["req_id"] for r in stats.get("records", [])
               if r["verdict"] == VERDICT_OK}
    rmses = [float(np.sqrt(np.mean((m[1:, :2] - t[1:, :2]) ** 2)))
             for i, (m, t) in enumerate(zip(stats["results"], truths))
             if m is not None and ("records" not in stats
                                   or i in healthy)]
    stats["mean_rmse"] = float(np.mean(rmses)) if rmses else None
    if rmses:
        emit(f"[serve/smoother] mean position RMSE {stats['mean_rmse']:.4f}")
    return stats


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", choices=("decode", "smoother"),
                   default="decode")
    p.add_argument("--arch", default=None, help="decode: model architecture")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--gen", type=int, default=16)
    p.add_argument("--reduced", action="store_true", default=True)
    p.add_argument("--requests", type=int, default=64)
    p.add_argument("--n", type=int, default=512)
    p.add_argument("--max-batch", type=int, default=64)
    p.add_argument("--method", choices=("ekf", "slr"), default="ekf")
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--tol", type=float, default=1e-6)
    p.add_argument("--sequential", action="store_true",
                   help="smoother: use the sequential baseline pass")
    p.add_argument("--f32", action="store_true",
                   help="smoother: run in float32")
    p.add_argument("--arrival", choices=("none", "poisson", "bursty"),
                   default="none",
                   help="smoother: request arrival process "
                        "(none = one-shot batch)")
    p.add_argument("--policy", choices=("static", "deadline"),
                   default="static",
                   help="smoother: bucket flush policy for streaming mode")
    p.add_argument("--rate", type=float, default=8.0,
                   help="smoother: offered load, requests/s")
    p.add_argument("--burst-size", type=int, default=8)
    p.add_argument("--deadline", type=float, default=2.0,
                   help="smoother: per-request completion budget (s)")
    p.add_argument("--max-wait", type=float, default=0.25,
                   help="smoother: queue-wait cap (s)")
    p.add_argument("--tenants", type=str, default=None,
                   help="smoother: comma-separated scenario[:slo[:weight]]"
                        " list (e.g. coordinated_turn,pendulum:gold) — "
                        "serves a mixed multi-tenant stream")
    p.add_argument("--chaos", type=float, default=0.0, metavar="RATE",
                   help="smoother: inject the seeded fault mix at this "
                        "headline rate (NaN payloads + transient "
                        "exceptions + stragglers; streaming mode only)")
    p.add_argument("--chaos-seed", type=int, default=0)
    args = p.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.workload == "smoother":
        cfg = SmootherServeConfig(
            requests=args.requests, n=args.n, max_batch=args.max_batch,
            method=args.method, n_iter=args.iters, tol=args.tol,
            parallel=not args.sequential, f64=not args.f32,
            arrival=args.arrival, policy=args.policy, rate=args.rate,
            burst_size=args.burst_size, deadline_s=args.deadline,
            max_wait_s=args.max_wait, chaos_rate=args.chaos,
            chaos_seed=args.chaos_seed)
        if args.chaos > 0 and args.arrival == "none":
            p.error("--chaos requires a streaming arrival process "
                    "(--arrival poisson|bursty)")
        if args.tenants:
            serve_smoother_multitenant(
                cfg, [TenantSpec.parse(s)
                      for s in args.tenants.split(",") if s])
        else:
            serve_smoother(cfg)
    else:
        if args.arch is None:
            p.error("--arch is required for the decode workload")
        serve(ServeConfig(arch=args.arch, batch=args.batch,
                          prompt_len=args.prompt_len, gen=args.gen,
                          reduced=args.reduced))


if __name__ == "__main__":
    main()
