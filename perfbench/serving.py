"""Drives the system under test: set-up through the program's own serving
entry points, then the closed loop of one cell.

Set-up: the program's ArchConfig (the architecture module's
``program_config``), weights from the seed (``weights.make``),
``serve.prepare_params``
(static scales from calibration, the fused qdot backend, merged
projections), the per-slot decode state, AOT compiles of the decode step
and of the B = 1 prefill at every prompt length the run's requests have,
the slot fill (each first request prefilled alone and scattered into its
slot with ``serve._scatter_slot``, as the program's continuous batching
does), the compiled Design #2 qdot calls of the integer check
(qdot_check.py), and one warm decode step.

The window: decode steps over all slots; after each, the tokens come to
the host, every finished request is replaced at once by the next queued
one (B = 1 prefill + scatter), and the window closes at the end of the
first step that ends at or after the requested seconds.  Host spans
(``jax.profiler.TraceAnnotation``) name what the host is doing:
decode_step, host_tokens, refill_prefill, scatter.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import arch as arch_mod
import faults
import qdot_check
import traffic
import weights as weights_mod

SPAN = jax.profiler.TraceAnnotation


def serve_argv(cfg: dict) -> list:
    """The serving CLI options the configuration states."""
    s, c = cfg["serving"], cfg["serving"]["calibration"]
    return ["--arch", cfg["name"], "--design", s["design"],
            "--quant-mode", s["quant_mode"], "--calibrate",
            str(c["batches"]), "--clip", c["clip"], "--requests",
            str(c["batch"]), "--prompt-len", str(c["prompt_tokens"])]


def calibration_prompts(cfg: dict) -> np.ndarray:
    """The calibration batch the configuration states (one batch)."""
    c = cfg["serving"]["calibration"]
    return np.random.default_rng(c["prompt_seed"]).integers(
        0, cfg["vocab_size"], (c["batch"], c["prompt_tokens"])).astype(
            np.int32)


@dataclasses.dataclass
class Window:
    start: float
    end: float
    steps: int
    positions: list          # per step: (B,) cache position of each slot
    requests: list           # every request that produced a token
    refills: int
    compiles: int            # compile requests inside the window

    def tokens_in(self) -> int:
        return sum(1 for r in self.requests for t in r.times
                   if self.start < t <= self.end)

    def gaps(self) -> list:
        """Gaps between consecutive tokens of one request that end in the
        window, in seconds."""
        return [b - a for r in self.requests
                for a, b in zip(r.times, r.times[1:])
                if self.start < b <= self.end]


class CompileCounter:
    """Counts compile requests and, of them, the persistent-cache misses
    (programs compiled here) through jax.monitoring."""

    def __init__(self):
        self.requests = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._event)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Server:
    """One cell's system under test, from set-up to the end of the window."""

    def __init__(self, cfg: dict, mix: dict, seed: int,
                 fault: str | None = None):
        from repro.launch import serve
        self.serve = serve
        self.cfg, self.mix, self.seed, self.fault = cfg, mix, seed, fault
        self.arch = arch_mod.load(cfg).program_config(cfg)
        self.args = serve.parse_args(serve_argv(cfg))
        self.qcfg = serve.quant_config(self.args)
        self.slots = mix["streams"]
        self.s_max = traffic.cache_rows(mix)
        self.first, self.queue = traffic.closed_loop(
            mix, cfg["vocab_size"], seed)
        self.compiles = CompileCounter()

    # -- set-up ---------------------------------------------------------
    def setup(self) -> dict:
        """Everything before the first timed step.  Returns set-up
        counts (compile requests, persistent-cache misses)."""
        from repro.kernels import platform
        from repro.models import transformer as T
        from repro.train import make_prefill_step, make_serve_step
        self.T = T
        params = weights_mod.make(self.cfg, self.seed)
        self.sites = qdot_check.operands(params, self.cfg, self.seed,
                                         self.slots)
        self.params, self.notes = self.serve.prepare_params(
            params, self.arch, self.qcfg, self.args)
        del params
        jax.block_until_ready(self.params)

        B, cfg = self.slots, self.arch
        state_spec = jax.eval_shape(
            lambda: T.init_decode_state(cfg, B, self.s_max, per_slot=True))
        one_spec = jax.eval_shape(
            lambda: T.init_decode_state(cfg, 1, self.s_max, per_slot=True))
        donate = platform.donate(1)
        self.step = jax.jit(make_serve_step(cfg, self.qcfg),
                            donate_argnums=donate).lower(
            self.params, state_spec,
            jax.ShapeDtypeStruct((B, 1), jnp.int32)).compile()
        lengths = sorted({len(r.prompt) for r in self.first + self.queue})
        prefill = jax.jit(make_prefill_step(cfg, self.qcfg),
                          donate_argnums=donate)
        self.prefill = {
            P: prefill.lower(self.params, one_spec,
                             jax.ShapeDtypeStruct((1, P), jnp.int32)).compile()
            for P in lengths}
        with faults.table(self.fault):
            self.qdot_calls = qdot_check.program_calls(
                self.sites, qdot_check.program_config(self.qcfg, self.fault))

        self.state = T.init_decode_state(cfg, B, self.s_max, per_slot=True)
        self.tok = jnp.zeros((B, 1), jnp.int32)
        self.slot_req = [None] * B
        self.pos = np.zeros(B, np.int64)
        self.requests = []
        self.next_q = 0
        for b, r in enumerate(self.first):
            self._fill(b, r)
        gc.collect()
        gc.freeze()            # no collector pause inside the window
        self._step()                                  # warm step
        return {"compile_requests": self.compiles.requests,
                "cache_misses": self.compiles.misses}

    def _fill(self, b: int, r: traffic.Request) -> None:
        """Prefill request ``r`` alone and scatter it into slot ``b``."""
        with SPAN("refill_prefill"):
            one = self.T.init_decode_state(self.arch, 1, self.s_max,
                                           per_slot=True)
            t1, _, one = self.prefill[len(r.prompt)](
                self.params, one, jnp.asarray(r.prompt[None]))
            first = int(np.asarray(t1)[0, 0])
        r.tokens.append(first)
        r.times.append(time.perf_counter())
        with SPAN("scatter"):
            self.state = self.serve._scatter_slot(self.state, one, b)
            self.tok = self.tok.at[b].set(t1[0])
        self.slot_req[b] = r
        self.pos[b] = len(r.prompt)
        self.requests.append(r)

    def _step(self) -> float:
        """One decode step over every slot; refills finished requests.
        Returns the host time at which its tokens were on the host."""
        with SPAN("decode_step"):
            self.tok, _, self.state = self.step(self.params, self.state,
                                                self.tok)
        with SPAN("host_tokens"):
            toks = np.asarray(self.tok)
        t = time.perf_counter()
        for b, r in enumerate(self.slot_req):
            r.tokens.append(int(toks[b, 0]))
            r.times.append(t)
            self.pos[b] += 1
        for b, r in enumerate(self.slot_req):
            if r.done:
                if self.next_q >= len(self.queue):
                    raise RuntimeError("the mix's queue ran out: raise "
                                       "its 'queue'")
                self._fill(b, self.queue[self.next_q])
                self.next_q += 1
        return t

    # -- the measured window --------------------------------------------
    def window(self, seconds: float) -> Window:
        c0 = self.compiles.requests
        refills0 = self.next_q
        gc.disable()
        try:
            start = time.perf_counter()
            positions = []
            while True:
                positions.append(self.pos.copy())
                t = self._step()
                if t - start >= seconds:
                    break
        finally:
            gc.enable()
        return Window(start, t, len(positions), positions,
                      list(self.requests), self.next_q - refills0,
                      self.compiles.requests - c0)

    # -- the Design #2 qdot calls (every run) and their int8 twins --------
    def run_qdot(self) -> dict:
        """The program's outputs of the integer check, after the window."""
        return {s.name: np.asarray(call()) for s, call in self.qdot_calls}

    def isolated_calls(self) -> list:
        """The traced run's isolated calls, one pair per projection of
        layer 0: the Design #2 qdot call of the integer check and a plain
        int8 x int8 -> int32 dot of the same (M, K, N).  Each entry:
        (projection, M, K, N, qdot call, int8 call)."""
        rng = np.random.default_rng(self.seed)
        calls = []
        for s, fq in self.qdot_calls:
            M, K, N = s.shape
            x8 = jnp.asarray(rng.integers(-128, 128, (M, K)), jnp.int8)
            w8 = jnp.asarray(rng.integers(-128, 128, (K, N)), jnp.int8)

            def g(x, w):
                return jax.lax.dot(x, w, preferred_element_type=jnp.int32)
            g.__name__ = f"bench_int8_{s.name}"
            g8 = jax.jit(g).lower(x8, w8).compile()
            calls.append((s.name, M, K, N, fq,
                          lambda g8=g8, x8=x8, w8=w8: g8(x8, w8)))
        return calls

    def free(self) -> None:
        """Release the program's device state (the reference runs next)."""
        for leaf in jax.tree.leaves((self.params, self.state, self.tok)):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                leaf.delete()
        self.params = self.state = self.tok = None
        self.step = self.prefill = self.qdot_calls = None
        gc.collect()
