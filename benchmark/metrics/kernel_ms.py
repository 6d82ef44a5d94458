"""Device milliseconds a step in one Pallas kernel, found by its ``name=``;
the slowest chip. The calls a step have to be a whole multiple of the
layers (one a layer, two for a forward that ``remat`` runs again): a count
that is not means the window cut a step or the name met something else. A
program whose kernels have no names reads as nothing; a named program
without this kernel is an error, never 0."""
import sys

from benchmark import program_trace as pt


def read(ctx, kernel):
    trace, steps = pt.of(ctx), ctx["window"].get("traced_steps")
    if trace is None or not steps or not pt.names_its_work(trace):
        return None
    per_dev = pt.device_ms(trace, steps, pt.kernel_filter(kernel))
    ms, calls = max(per_dev.values())
    layers = ctx["config"]["model"]["num_layers"]
    if ms <= 0:
        raise LookupError(f"no device event of a kernel named {kernel!r}")
    if calls % layers:
        raise LookupError(f"kernel {kernel!r}: {calls} calls a step over "
                          f"{layers} layers")
    print(f"benchmark: kernel {kernel}: {calls:g} calls a step "
          f"({calls / layers:g} a layer; remat "
          f"{ctx['config']['entry'].get('remat', False)}), {ms:.3f} ms",
          file=sys.stderr, flush=True)
    return ms
