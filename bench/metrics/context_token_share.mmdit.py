"""Model step: the share of the denoiser's tokens that are context tokens,
sum of ``ctx`` over sum of (``rows`` x tokens a row + ``ctx``) over the
``exec.model`` spans inside ``stadi.generate``, in %: the text stream that
every patch evaluation recomputes whole, which the planner's Eq. 4/5 does
not price. None where no model span carries ``ctx`` (a DiT, or a program
without the span argument)."""
from bench import harness, program_trace


def context_share(spans, tokens_per_row: int):
    """The share over program spans [(start, end, name, args)]."""
    calls = [(a, b) for a, b, name, _ in spans if name == "stadi.generate"]
    ctx = total = 0
    for a, _, name, args in spans:
        if (name == "exec.model" and "ctx" in args
                and any(c0 <= a < c1 for c0, c1 in calls)):
            ctx += args["ctx"]
            total += args["rows"] * tokens_per_row + args["ctx"]
    return 100.0 * ctx / total if total else None


def read(run):
    events = program_trace.read(harness._newest_xplane())
    wp = run.sizes["latent_size"] // run.sizes["patch_size"]
    return context_share(events["spans"], wp)
