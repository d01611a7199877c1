"""Closed loop of one client calling ``StadiPipeline.generate`` back to back.

Traffic parameters: ``stadi`` (the StadiConfig knobs: occupancies, m_base,
m_warmup, planner, backend, exchange). With ``backend: spmd`` the weights
are replicated on a mesh over ``jax.devices()``. Request ``k`` of a run is
``request_input(seed, k)``; every call does the same work.
"""
import time

WARM_K = 1_000_000                      # request id of the warm-up call


class Driver:
    SPANS = ("generate",)

    def __init__(self, run):
        self.run = run
        self.records = []
        self.t_window = None

    def weights_sharding(self):
        if self.run.traffic["stadi"]["backend"] != "spmd":
            return None
        import jax
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        mesh = Mesh(np.asarray(jax.devices()), ("dev",))
        return NamedSharding(mesh, PartitionSpec())

    def setup(self):
        from repro.core import sampler
        from repro.core.pipeline import StadiPipeline
        run = self.run
        self.pipe = StadiPipeline(run.program_config(), run.weights,
                                  sampler.linear_schedule(T=1000),
                                  run.stadi_config())
        self._call(WARM_K)                   # warm: every program compiles
        p = self.pipe.plan()
        self._plan = (p.temporal.m_base, p.temporal.m_warmup,
                      list(p.temporal.ratios), list(p.patches))

    def _call(self, k):
        import jax
        x_T, cls = self.run.request(k)
        x_T = jax.device_put(x_T).block_until_ready()
        with self.run.spans("generate"):
            t0 = time.perf_counter()
            res = self.pipe.generate(x_T, cls)
            image = jax.block_until_ready(res.image)
            t1 = time.perf_counter()
        return {"k": k, "t0": t0, "t1": t1, "image": image}

    def window(self, seconds):
        self.t_window = time.perf_counter()
        end = self.t_window + seconds
        k = 0
        while time.perf_counter() < end:
            self.records.append(self._call(k))
            k += 1
        self.t_close = time.perf_counter()

    def notes(self):
        d = [r["t1"] - r["t0"] for r in self.records]
        return [f"{len(d)} generate calls, {min(d):.4f}..{max(d):.4f}s each"]

    def plan(self):
        return self._plan

    def end_to_end(self):
        d = [r["t1"] - r["t0"] for r in self.records]
        return {"gen_latency_s": sum(d) / len(d)}

    def images_in_window(self):
        return len(self.records)

    def window_s(self):
        return self.t_close - self.t_window

    def outputs(self):
        import numpy as np
        return [{"k": r["k"], "image": np.asarray(r["image"])}
                for r in self.records]

    def attempted(self):
        return len(self.records)

    def failed(self):
        return 0

    def release(self):
        for r in self.records:
            r.pop("image", None)
        self.pipe = None
