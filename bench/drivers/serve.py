"""Open loop of independent users of an image service, through
``DiffusionServingEngine`` with a fixed number of slots.

Traffic parameters: ``stadi`` (StadiConfig knobs), ``slots``,
``rate_per_s`` (offered images per second) and ``drain_s`` (how long after
the window's close requests still due may finish; 0 = none, and what is
unfinished when the run stops counts as failed).

Arrivals are a Poisson process whose gaps are the ``n = rate * seconds``
quantiles of the exponential distribution, shuffled by the seed: every seed
offers the same set of gaps, so the same load, in another order. A request
is timed from when it was due, not from when it was submitted; the engine
runs whole rounds, so a request that falls due during a round is submitted
after it.
"""
import math
import time

import numpy as np

WARM_K = 1_000_000                      # request ids of the warm-up


def arrivals(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds after the window opens), all inside the window."""
    n = max(1, int(round(rate * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    gaps = np.random.default_rng([seed, 4]).permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def p90(latencies) -> float:
    """Nearest-rank 90th percentile."""
    xs = sorted(latencies)
    return xs[max(0, math.ceil(0.9 * len(xs)) - 1)]


class Driver:
    SPANS = ("submit", "engine.step", "wait_arrival")

    def __init__(self, run):
        self.run = run
        t = run.traffic
        self.slots, self.rate = t["slots"], t["rate_per_s"]
        self.drain_s = t["drain_s"]

    def weights_sharding(self):
        return None

    def setup(self):
        from repro.core import sampler
        from repro.core.pipeline import StadiPipeline
        run = self.run
        self.pipe = StadiPipeline(run.program_config(), run.weights,
                                  sampler.linear_schedule(T=1000),
                                  run.stadi_config())
        # warm every lane kind: warm-up-only rounds, mixed rounds and
        # adaptive-only rounds, every slot admitted and retired
        engine = self._engine()
        for i in range(self.slots):
            engine.submit(*run.request(WARM_K + i))
        for _ in range(2):
            engine.step()
        for i in range(self.slots):
            engine.submit(*run.request(WARM_K + self.slots + i))
        engine.run_to_completion()
        self.engine = self._engine()
        p = self.engine.plan
        self._plan = (p.temporal.m_base, p.temporal.m_warmup,
                      list(p.temporal.ratios), list(p.patches))

    def _engine(self):
        from repro.serving.diffusion_engine import DiffusionServingEngine
        return DiffusionServingEngine(self.pipe, slots=self.slots)

    def window(self, seconds):
        run, engine = self.run, self.engine
        due = arrivals(self.rate, seconds, run.seed)
        self.due = due
        self.ready = np.full(len(due), np.inf)
        self.submitted = np.full(len(due), np.nan)
        self.round_start, self.round_s = [], []
        self.req_of = {}
        clock = time.perf_counter
        t0 = self.t_window = clock()
        self.t_close = t0 + seconds
        stop = self.t_close + self.drain_s
        nxt = 0
        while True:
            now = clock()
            while nxt < len(due) and t0 + due[nxt] <= now:
                x_T, cls = run.request(nxt)
                with run.spans("submit"):
                    req = engine.submit(x_T, cls)
                self.req_of[req.uid] = nxt
                self.submitted[nxt] = clock() - t0
                nxt += 1
            if engine.queue or engine.active:
                if now >= stop:
                    break
                r0 = clock()
                with run.spans("engine.step"):
                    finished = engine.step()
                r1 = clock()
                self.round_start.append(r0 - t0)
                self.round_s.append(r1 - r0)
                for req in finished:
                    self.ready[self.req_of[req.uid]] = r1 - t0
            elif nxt < len(due):
                with run.spans("wait_arrival"):
                    time.sleep(max(0.0, t0 + due[nxt] - clock()))
            elif now >= self.t_close:
                break
            else:
                time.sleep(self.t_close - now)
        self.t_stop = clock() - t0

    def _latencies(self):
        """Due to ready; an unfinished request counts from due to the
        run's stop, a lower bound of its latency."""
        return np.where(np.isfinite(self.ready), self.ready,
                        self.t_stop) - self.due

    def notes(self):
        late = self.submitted - self.due
        late = late[np.isfinite(late)]
        done = np.isfinite(self.ready)
        return [f"{len(self.due)} requests due, {int(done.sum())} finished, "
                f"{len(self.round_s)} rounds",
                f"generator lateness (submit - due): mean {late.mean():.4f}s, "
                f"max {late.max():.4f}s"]

    def plan(self):
        return self._plan

    def end_to_end(self):
        seconds = self.t_close - self.t_window
        return {"serve_p90_s": p90(self._latencies()),
                "serve_images_per_s": self.images_in_window() / seconds}

    def images_in_window(self):
        return int(np.sum(self.ready <= self.t_close - self.t_window))

    def window_s(self):
        return self.t_close - self.t_window

    def queue_wait_s(self):
        """Mean of (start of the admitting round - due) over admitted
        requests."""
        waits = [self.round_start[r.admit_round] - self.due[self.req_of[r.uid]]
                 for r in self.engine.completed + list(self.engine.active.values())]
        return float(np.mean(waits)) if waits else None

    def outputs(self):
        return [{"k": self.req_of[r.uid], "image": np.asarray(r.image)}
                for r in self.engine.completed]

    def attempted(self):
        return len(self.due)

    def failed(self):
        return int(np.sum(~np.isfinite(self.ready)))

    def release(self):
        self.engine = None
        self.pipe = None
