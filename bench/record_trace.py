"""Record the small chip trace from which the benchmark's CPU tests read the
program's spans (``tests/bench/data/program_spans.xplane.pb.xz``): inside a
harness ``window`` span, one ``generate`` and three serving rounds (three
requests in four slots) of a narrow, shallow DiT on one chip, with the
harness's profiler options.

    python bench/record_trace.py --out program_spans.xplane.pb.xz

An ``--out`` ending in ``.xz`` is written xz-compressed (the raw trace is
about 3.4 MB on a TPU v5e). Prints the program-trace summary lines. Exits
non-zero without an accelerator. The benchmark's own runs do not run this.
"""
import argparse
import lzma
import os
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness, program_trace  # noqa: E402

SLOTS, REQUESTS, ROUNDS = 4, 3, 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    harness.prepare_environment()
    import jax
    from repro.configs import get_config
    from repro.core import sampler
    from repro.core.pipeline import StadiConfig, StadiPipeline
    from repro.models.diffusion import dit
    from repro.serving import DiffusionServingEngine
    try:
        harness.check_devices(1)
    except harness.BenchError as e:
        print(f"[bench] no trace: {e}", file=sys.stderr)
        return 2

    cfg = get_config("tiny-dit").reduced()
    pipe = StadiPipeline(cfg, dit.init_params(jax.random.PRNGKey(0), cfg),
                         sampler.linear_schedule(T=1000),
                         StadiConfig.from_occupancies([0.0, 0.6], m_base=6,
                                                      m_warmup=2))
    shape = (1, cfg.latent_size, cfg.latent_size, cfg.channels)
    xs = [jax.random.normal(jax.random.PRNGKey(k), shape)
          for k in range(REQUESTS)]

    def engine():
        e = DiffusionServingEngine(pipe, slots=SLOTS)
        for k, x in enumerate(xs):
            e.submit(x, k % cfg.n_classes)
        return e

    def work(e):
        jax.block_until_ready(pipe.generate(xs[0], 0).image)
        for _ in range(ROUNDS):
            e.step()
        jax.block_until_ready((e._x, e._pub_k, e._pub_v))

    work(engine())                       # every program compiles here
    e = engine()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=harness._profile_options())
        with jax.profiler.TraceAnnotation(program_trace.WINDOW):
            work(e)
        jax.profiler.stop_trace()
        (src,) = Path(d).glob("plugins/profile/*/*.xplane.pb")
        s = program_trace.summarize(program_trace.read(str(src)))
        raw = src.read_bytes()
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_bytes(lzma.compress(raw, preset=9 | lzma.PRESET_EXTREME)
                    if out.suffix == ".xz" else raw)
    print(f"[bench] {out}: {out.stat().st_size} bytes ({len(raw)} raw)",
          file=sys.stderr)
    for line in program_trace.report(s):
        print(f"[bench] {line}", file=sys.stderr)
    print(s)
    return 0


if __name__ == "__main__":
    sys.exit(main())
