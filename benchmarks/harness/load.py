"""The load generator: one thread that submits `Request`s to the scheduler.

Open loop: each request is submitted at its due time and timed from it; how
late the generator ran is recorded. Closed loop: each of a fixed number of
clients sends the next request of the list when its last one completes. All
timing is `time.monotonic()`, the clock `Request.submitted_at` and
`admitted_at` are stamped with.

The only work done on the scheduler's own thread is the `on_delta` callback
(one clock read and one list append) and the future's done-callback (one
queue put).
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time

from .traffic import RequestSpec, Traffic


class Stream:
    """What the client side saw of one request."""

    __slots__ = ("spec", "req", "due_t", "submit_t", "delta_t")

    def __init__(self, spec: RequestSpec):
        self.spec = spec
        self.req = None
        self.due_t = None      # open loop: when it was due; closed: None
        self.submit_t = None   # when the generator handed it to submit()
        self.delta_t: list[float] = []

    @property
    def start_t(self) -> float:
        """What time-to-first-token counts from: the due time in an open
        loop, the moment the client sent it in a closed one."""
        return self.due_t if self.due_t is not None else self.submit_t


class LoadGenerator:
    def __init__(self, sched, traffic: Traffic, seed: int, vocab_size: int,
                 prompts: dict, annotate=None):
        from distributed_llama_multiusers_tpu.runtime.scheduler import Request

        self._Request = Request
        self.sched = sched
        self.traffic = traffic
        self.seed = seed
        self.vocab_size = vocab_size
        self.prompts = prompts          # the tokenizer's table: text -> ids
        # host spans on the profiler's clock in a traced run, else nothing
        self._span = annotate or (lambda name: contextlib.nullcontext())
        self._traced = annotate is not None
        self.streams: list[Stream] = []
        self.t0 = None
        self._next_k = 0
        self._halt = threading.Event()
        self._done_q: queue.Queue = queue.Queue()
        self._thread = None
        self.error = None

    # -- one request ------------------------------------------------------

    def _submit(self, due_t) -> None:
        k = self._next_k
        self._next_k += 1
        spec = self.traffic.spec(k)
        st = Stream(spec)
        text = f"#{k}"
        self.prompts[text] = self.traffic.token_ids(self.seed, k, self.vocab_size)
        mono, times = time.monotonic, st.delta_t

        def on_delta(_delta, _mono=mono, _append=times.append):
            _append(_mono())

        if self._traced:  # the callback as a span on the profiler's clock
            plain, span = on_delta, self._span

            def on_delta(delta):
                with span("bench.callback"):
                    plain(delta)

        req = self._Request(
            prompt=text,
            max_tokens=spec.max_tokens,
            temperature=spec.temperature,
            topp=spec.top_p,
            seed=self.traffic.sampler_seed(self.seed, k),
            add_bos=False,
            on_delta=on_delta,
        )
        st.req = req
        st.due_t = due_t
        req.future.add_done_callback(lambda _f, st=st: self._on_done(st))
        self.streams.append(st)
        with self._span("bench.submit"):
            st.submit_t = time.monotonic()
            self.sched.submit(req)

    def _on_done(self, st: Stream) -> None:
        self.prompts.pop(st.req.prompt, None)
        self._done_q.put(st)

    # -- the two loops ----------------------------------------------------

    def _run_open(self) -> None:
        for _ in range(self.traffic.in_flight):
            self._submit(self.t0)
        while not self._halt.is_set():
            due_t = self.t0 + self.traffic.spec(self._next_k).due_s
            with self._span("bench.wait_due"):
                while True:
                    left = due_t - time.monotonic()
                    if left <= 0 or self._halt.is_set():
                        break
                    self._halt.wait(min(left, 0.05) if left > 0.002 else 0)
            if self._halt.is_set():
                return
            self._submit(due_t)

    def _run_closed(self) -> None:
        for _ in range(self.traffic.clients):
            self._submit(None)
        while not self._halt.is_set():
            try:
                with self._span("bench.wait_done"):
                    self._done_q.get(timeout=0.05)
            except queue.Empty:
                continue
            if self._halt.is_set():
                return
            self._submit(None)

    def _run(self) -> None:
        try:
            if self.traffic.loop == "open":
                self._run_open()
            else:
                self._run_closed()
        except BaseException as e:  # surfaced by stop(); the run then fails
            self.error = e

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> float:
        self.t0 = time.monotonic()
        self._thread = threading.Thread(
            target=self._run, name="bench-load", daemon=True
        )
        self._thread.start()
        return self.t0

    def halt(self) -> None:
        """Stop submitting (what is in flight goes on)."""
        self._halt.set()
        self._thread.join(timeout=10)
        if self._thread.is_alive():
            raise RuntimeError("the load generator's thread did not stop")
        if self.error is not None:
            raise self.error

    def cancel_outstanding(self, timeout: float = 60.0) -> None:
        """Cancel every request still running or queued and wait for each
        future, so the scheduler holds nothing when it stops."""
        pending = [s for s in self.streams if not s.req.future.done()]
        for s in pending:
            s.req.cancel()
        deadline = time.monotonic() + timeout
        for s in pending:
            s.req.future.result(timeout=max(0.1, deadline - time.monotonic()))
