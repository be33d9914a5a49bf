"""The one traffic generator: it reads a traffic file's parameters
(``bench/traffic/<name>.json``) and drives the engine in closed loop.

Two kinds, by the file's ``kind``:

- ``oneshot``: ``clients`` closed-loop clients, each with one request at
  a time in flight through ``InferenceEngine.submit``, all driven from
  one thread.  Inputs come from a
  pool of ``inputs`` tensors made from the seed; client ``c``'s ``k``-th
  request takes pool entry ``perm[(c + k * clients) % inputs]``.
- ``decode``: ``sessions`` threads, each running one
  ``InferenceEngine.generate`` session at a time until its KV capacity is
  full or the window ends, then opening the next context of a pool of
  ``pool``.  Slot ``j`` takes pool contexts ``j``, ``j + sessions``, ...
  Prompt lengths are fixed quantiles of a lognormal (``prompt_median``,
  ``prompt_sigma``) clipped to ``[prompt_min, prompt_max]``.  With
  ``aged_up_to`` (a share of the KV capacity), each context is its prompt
  followed by a seeded continuation that brings the session to a start
  depth spread over ``[prompt, aged_up_to * capacity)``: the window opens
  on sessions already deep into their decode, as a server in steady state
  holds them, rather than all at their prompts.  Every seed gets the same
  lengths and depths, so the same compiled programs, in another order and
  with other token ids.

Times are ``time.perf_counter()`` seconds.  A request or token counts in
the window when it completes at or before the window's end; requests in
flight at the end are waited for and kept for the correctness check.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
import threading
import time
from concurrent.futures import FIRST_COMPLETED, Future, wait
from typing import Any, Callable

import numpy as np

RESULT_TIMEOUT_S = 120.0
WARM_ROUNDS = 6
WARM_COALESCE_S = 0.25
WARM_STEP_TIMEOUT_S = 600.0   # a warm-up step may wait on cold compiles
DEPTH_ORDER_SEED = 0    # pairs prompt lengths with start depths, for all seeds


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), stream])


# -- one-shot requests --------------------------------------------------------

@dataclasses.dataclass
class Request:
    client: int
    k: int
    index: int                  # pool entry
    t_submit: float
    t_done: float = math.nan
    output: np.ndarray | None = None
    error: str | None = None


class Oneshot:
    def __init__(self, traffic: dict, input_shape: tuple[int, ...],
                 seed: int):
        import jax
        from bench.weights import seed_key
        self.clients = int(traffic["clients"])
        self.warmup_passes = int(traffic.get("warmup_passes", 1))
        n = int(traffic["inputs"])
        key = jax.random.fold_in(seed_key(seed), 1)
        # one device call for the whole pool, one copy to the host
        self.pool = np.asarray(jax.jit(
            lambda k: jax.random.normal(k, (n,) + tuple(input_shape)),
        )(key))
        self.perm = seed_rng(seed, 1).permutation(n)

    def index(self, client: int, k: int) -> int:
        return int(self.perm[(client + k * self.clients) % len(self.perm)])

    def _closed_loop(self, engine, until: Callable[[int, int], bool]
                     ) -> list[Request]:
        """Every client from one thread: each client has one request in
        flight and sends its ``k``-th as soon as its previous one is
        back, until ``until(client, k)``.  One thread, not one per client,
        so the load generator's own threads do not contend with the
        program's for the interpreter."""
        out: list[Request] = []
        pending: dict[Future, Request] = {}
        sent = [0] * self.clients

        def send(c: int) -> None:
            i = self.index(c, sent[c])
            r = Request(c, sent[c], i, time.perf_counter())
            sent[c] += 1
            try:
                with _annotate("bench.submit"):
                    pending[engine.submit(self.pool[i], client_id=c)] = r
            except Exception as e:  # noqa: BLE001 - reported per request
                r.error = f"{type(e).__name__}: {e}"
                r.t_done = time.perf_counter()
                out.append(r)

        for c in range(self.clients):
            if not until(c, 0):
                send(c)
        while pending:
            with _annotate("bench.result"):
                done, _ = wait(pending, timeout=RESULT_TIMEOUT_S,
                               return_when=FIRST_COMPLETED)
            if not done:
                raise TimeoutError(f"no result in {RESULT_TIMEOUT_S} s "
                                   f"({len(pending)} requests in flight)")
            now = time.perf_counter()
            for fut in done:
                r = pending.pop(fut)
                r.t_done = now
                try:
                    r.output = np.asarray(fut.result())
                except Exception as e:  # noqa: BLE001 - reported per request
                    r.error = f"{type(e).__name__}: {e}"
                out.append(r)
                if not until(r.client, sent[r.client]):
                    send(r.client)
        return out

    def warm(self, engine, compiled: Callable[[], int]) -> None:
        """``warmup_passes`` closed-loop passes at the cell's concurrency:
        every client sends that many requests."""
        res = self._closed_loop(engine, lambda c, k: k >= self.warmup_passes)
        errors = [r.error for r in res if r.error]
        if errors:
            raise RuntimeError(f"warm-up request failed: {errors[0]}")

    def window(self, engine, t_end: float) -> list[Request]:
        return self._closed_loop(engine,
                                 lambda c, k: time.perf_counter() >= t_end)


def oneshot_metrics(reqs: list[Request], t0: float, t_end: float) -> dict:
    done = [r for r in reqs if r.error is None]
    in_window = sum(1 for r in done if r.t_done <= t_end)
    lat_ms = [1e3 * (r.t_done - r.t_submit) for r in done]
    return {"requests_per_s": in_window / (t_end - t0),
            "request_p95_ms": _p95(lat_ms)}


# -- decode sessions ----------------------------------------------------------

def prompt_lengths(traffic: dict) -> list[int]:
    """The pool's prompt lengths: fixed lognormal quantiles, clipped."""
    n = int(traffic["pool"])
    med, sigma = float(traffic["prompt_median"]), float(traffic["prompt_sigma"])
    lo, hi = int(traffic["prompt_min"]), int(traffic["prompt_max"])
    nd = statistics.NormalDist()
    return [min(hi, max(lo, round(med * math.exp(
        sigma * nd.inv_cdf((i + 0.5) / n))))) for i in range(n)]


def start_depths(traffic: dict, capacity: int) -> list[int]:
    """Each pool context's length: its prompt's, or with ``aged_up_to`` a
    start depth at a fixed share of ``[prompt, aged_up_to * capacity)``.
    The shares are evenly spaced and paired with the prompt lengths in a
    fixed order, the same for every seed."""
    lengths = prompt_lengths(traffic)
    if "aged_up_to" not in traffic:
        return lengths
    top = int(float(traffic["aged_up_to"]) * capacity)
    n = len(lengths)
    order = np.random.default_rng(DEPTH_ORDER_SEED).permutation(n)
    return [p + int((order[i] + 0.5) / n * max(0, top - p))
            for i, p in enumerate(lengths)]


@dataclasses.dataclass
class Session:
    slot: int
    n: int
    prompt: int                 # pool index of its context
    t_open: float
    tokens: list[int] = dataclasses.field(default_factory=list)
    times: list[float] = dataclasses.field(default_factory=list)
    error: str | None = None


class Decode:
    def __init__(self, traffic: dict, cfg: dict, seed: int):
        self.sessions = int(traffic["sessions"])
        self.warmup_tokens = int(traffic.get("warmup_tokens", 8))
        self.capacity = int(cfg["max_position_embeddings"])
        lengths = start_depths(traffic, self.capacity)
        rng = seed_rng(seed, 2)
        order = rng.permutation(len(lengths))
        vocab = int(cfg["vocab_size"])
        # each session's context: its prompt and, for an aged pool, the
        # tokens it had decoded before the window, as one prefill
        self.prompts = [rng.integers(0, vocab, lengths[i]).astype(np.int32)
                        for i in order]

    def max_new(self, prompt: int) -> int:
        return self.capacity - len(self.prompts[prompt])

    def _session(self, engine, slot: int, n: int, prompt: int,
                 max_new: int, t_end: float | None,
                 barrier: threading.Barrier | None = None,
                 step_timeout: float = RESULT_TIMEOUT_S) -> Session:
        """One session; with a ``barrier``, every session of the group waits
        there after its first token, so their steps leave together."""
        s = Session(slot, n, prompt, time.perf_counter())
        gen = engine.generate(self.prompts[prompt], max_new,
                              session_id=f"bench-{slot}-{n}-{prompt}",
                              step_timeout=step_timeout)
        try:
            while True:
                with _annotate("bench.generate"):
                    tok = next(gen, None)
                if tok is None:
                    break
                t = time.perf_counter()
                s.tokens.append(int(tok))
                s.times.append(t)
                if t_end is not None and t >= t_end:
                    break
                if barrier is not None and len(s.tokens) == 1:
                    barrier.wait()
        except Exception as e:  # noqa: BLE001 - reported per session
            s.error = f"{type(e).__name__}: {e}"
            if barrier is not None:
                barrier.abort()         # the group's others stop waiting
        finally:
            gen.close()
        return s

    def warm(self, engine, compiled: Callable[[], int]) -> None:
        """Every pool context's prefill, then every decode wave size the
        window can form.  First each context alone, two tokens, one after
        another: each prefill length compiles once, and no session waits
        on the compiles of others.  Then for each power of two ``b`` up to
        ``sessions`` (and ``sessions`` itself), ``b`` concurrent sessions
        of ``warmup_tokens`` tokens whose steps leave together and, with
        each stage's ``coalesce_s`` raised to ``WARM_COALESCE_S``
        meanwhile (through the dispatcher's ``set_stage_knobs``), reach
        every stage as one wave; again until a round after the first
        compiled nothing (``compiled()`` counts compiles so far; at most
        ``WARM_ROUNDS`` rounds).  ``coalesce_s`` is put back before the
        window."""
        for p in range(len(self.prompts)):
            self._warm_group(engine, [p], 2)
        sizes, b = [], 1
        while b < self.sessions:
            sizes.append(b)
            b *= 2
        sizes.append(self.sessions)
        nxt = 0
        stages = {n["stage"]: n["coalesce_s"]
                  for n in engine.report().per_node}
        for i in stages:            # each group's steps in one wave
            engine.dispatcher.set_stage_knobs(i, coalesce_s=WARM_COALESCE_S)
        try:
            for b in sizes:
                picks = [(nxt + i) % len(self.prompts) for i in range(b)]
                nxt += b
                for r in range(WARM_ROUNDS):
                    before = compiled()
                    self._warm_group(engine, picks, self.warmup_tokens,
                                     together=True)
                    if r > 0 and compiled() == before:
                        break
        finally:
            for i, c in stages.items():
                engine.dispatcher.set_stage_knobs(i, coalesce_s=c)

    def _warm_group(self, engine, picks: list[int], tokens: int,
                    together: bool = False) -> None:
        done: list[Session] = [None] * len(picks)  # type: ignore[list-item]
        barrier = threading.Barrier(len(picks)) if together else None

        def one(i: int) -> None:
            done[i] = self._session(engine, i, -1, picks[i], tokens, None,
                                    barrier, WARM_STEP_TIMEOUT_S)

        _run_threads(one, len(picks))
        errors = sorted((s.error for s in done if s.error),
                        key=lambda e: e.startswith("BrokenBarrierError"))
        if errors:
            raise RuntimeError(f"warm-up session failed: {errors[0]}")

    def window(self, engine, t_end: float) -> list[Session]:
        out: list[list[Session]] = [[] for _ in range(self.sessions)]

        def slot(j: int) -> None:
            n = 0
            while time.perf_counter() < t_end:
                p = (j + n * self.sessions) % len(self.prompts)
                out[j].append(self._session(engine, j, n, p, self.max_new(p),
                                            t_end))
                n += 1

        _run_threads(slot, self.sessions)
        return [s for ss in out for s in ss]


def decode_metrics(sessions: list[Session], t0: float, t_end: float) -> dict:
    tokens = sum(1 for s in sessions for t in s.times if t <= t_end)
    gaps_ms = [1e3 * (b - a) for s in sessions
               for a, b in zip(s.times, s.times[1:]) if b <= t_end]
    return {"tokens_per_s": tokens / (t_end - t0),
            "itl_p95_ms": _p95(gaps_ms)}


# -- shared -------------------------------------------------------------------

def _p95(values: list[float]) -> float:
    from bench.stats import percentile
    return percentile(values, 95) if values else math.nan


def _run_threads(fn: Callable[[int], Any], n: int) -> None:
    """Run ``fn(i)`` for i in range(n) on n threads; re-raise the first
    error any of them raised."""
    errors: list[BaseException] = []

    def wrap(i: int) -> None:
        try:
            fn(i)
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errors.append(e)

    threads = [threading.Thread(target=wrap, args=(i,), name=f"bench-{i}")
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


DRIVERS = {"oneshot": Oneshot, "decode": Decode}
