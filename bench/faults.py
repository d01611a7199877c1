"""Faults planted under the timed path, for the checks that ``correct``
comes out false when the program is broken: each is a function of a
``setattr(obj, name, value)`` (pytest's ``monkeypatch.setattr``, or
``Planted.setattr`` on the chip), named by the cell kind it applies to."""
from __future__ import annotations


def unchanged_state(setattr):
    """Every DDIM update returns its input: the latent never moves."""
    from repro.core import sampler
    setattr(sampler, "ddim_step", lambda sched, x, eps, t0, t1: x)


def half_the_lanes(setattr):
    """The second half of each padded lane group keeps its old latent."""
    from repro.serving.diffusion_engine import EmulatedStepper
    orig = EmulatedStepper.interval

    def half(self, xs, *a, **k):
        out, pk, pv = orig(self, xs, *a, **k)
        g = xs.shape[0] // 2
        return out.at[g:].set(xs[g:]), pk, pv
    setattr(EmulatedStepper, "interval", half)


def altered_answer_generate(setattr):
    """One latent row of every image zeroed where ``generate`` returns it."""
    from repro.core import patch_parallel as pp
    orig = pp.run_schedule

    def altered(*a, **k):
        res = orig(*a, **k)
        res.image = res.image.at[:, 0].set(0.0)
        return res
    setattr(pp, "run_schedule", altered)


def altered_answer_serve(setattr):
    """One latent row of every finished request zeroed as the engine
    hands it back."""
    from repro.serving.diffusion_engine import DiffusionServingEngine
    orig = DiffusionServingEngine.step

    def step(self):
        done = orig(self)
        for req in done:
            req.image = req.image.at[:, 0].set(0.0)
        return done
    setattr(DiffusionServingEngine, "step", step)


FAULTS = {
    "generate": {"unchanged_state": unchanged_state,
                 "altered_answer": altered_answer_generate},
    "serve": {"unchanged_state": unchanged_state,
              "half_the_lanes": half_the_lanes,
              "altered_answer": altered_answer_serve},
}


class Planted:
    """Plants faults by ``setattr`` and takes them all out again."""

    def __init__(self):
        self._undo = []

    def setattr(self, obj, name, value):
        self._undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def undo(self):
        for obj, name, value in reversed(self._undo):
            setattr(obj, name, value)
        self._undo.clear()
