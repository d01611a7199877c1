"""Closed loop of one client calling ``StadiPipeline.generate`` with a
prompt, back to back: the ``generate`` driver with two changes. The
pipeline samples with the rectified-flow schedule of the config's
``flow_shift``, and request ``k`` passes, besides ``x_T``, the prompt that
its id names in the reference's pool (``prompt``: the text encoders'
context and pooled vector), put on the device before the call as x_T is.
"""
import time

from bench.drivers import generate


class Driver(generate.Driver):

    def setup(self):
        from repro.core import sampler
        from repro.core.pipeline import StadiPipeline
        run = self.run
        self.pipe = StadiPipeline(run.program_config(), run.weights,
                                  sampler.FlowSchedule(
                                      shift=run.sizes["flow_shift"]),
                                  run.stadi_config())
        self._call(generate.WARM_K)          # warm: every program compiles
        p = self.pipe.plan()
        self._plan = (p.temporal.m_base, p.temporal.m_warmup,
                      list(p.temporal.ratios), list(p.patches))

    def _call(self, k):
        import jax
        from repro.models.diffusion.mmdit import TextCond
        x_T, cls = self.run.request(k)
        ctx, pooled = self.run.reference.prompt(self.run.sizes, cls)
        x_T, cond = jax.block_until_ready(jax.device_put(
            (x_T, TextCond(ctx[None], pooled[None]))))
        with self.run.spans("generate"):
            t0 = time.perf_counter()
            res = self.pipe.generate(x_T, cond)
            image = jax.block_until_ready(res.image)
            t1 = time.perf_counter()
        return {"k": k, "t0": t0, "t1": t1, "image": image}
