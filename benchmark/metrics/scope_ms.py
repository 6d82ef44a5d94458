"""Device milliseconds a step of the operations the program named: by the
phase of the step (forward, recompute, backward, optimizer: the one rule
of ``program_trace.phase_of``), under a scope of the program
(``jax.named_scope``: a component of the operation's ``op_name``), of an
opcode; the slowest chip. A program that names nothing reads as nothing;
one that does and lacks what is asked for is an error, never 0."""
from benchmark import program_trace as pt


def read(ctx, phase=None, scope=None, opcode=None):
    trace, steps = pt.of(ctx), ctx["window"].get("traced_steps")
    if trace is None or not steps or not pt.names_its_work(trace):
        return None
    per_dev = pt.device_ms(trace, steps, pt.scope_filter(phase, scope, opcode))
    ms = max(v for v, _ in per_dev.values())
    if ms <= 0:
        raise LookupError(f"no device operation of phase {phase!r} under "
                          f"scope {scope!r} with opcode {opcode!r}")
    return ms
