"""Share of the device's busy time in the traced slice spent in ops of the
program's ``scheduler_pass`` phase scope (``jax.named_scope`` inside
``process_batch``), the innermost phase in each op's ``op_name``. Without
phase scopes in the trace, nothing."""
import program_spans


def read(ctx):
    phases = program_spans.phase_s(ctx)
    busy = sum(ctx.trace.busy_s.values()) if ctx.trace is not None else 0.0
    if "scheduler_pass" not in phases or busy <= 0:
        return None
    return 100.0 * phases["scheduler_pass"] / busy
