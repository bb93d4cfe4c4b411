"""The share of their budget the Pallas CG solves ran: the iterations the
kernel's tiles ran before they had converged (ops/solve._cg_kernel) over the
iterations their caps allowed, both summed over the systems of the last user
and item half-sweeps this process dispatched (ops/als.last_cg_iterations:
the program's own count, fetched here, after the window). 100 is a solver
that runs every budget out; nothing to read where the program has no such
counter, or where no solve went through that kernel."""


def read(ctx):
    from predictionio_tpu.ops import als
    counted = getattr(als, "last_cg_iterations", lambda: None)()
    if not counted or not counted[1]:
        return None
    run, allowed = counted
    return 100.0 * run / allowed
