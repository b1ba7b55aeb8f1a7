"""Model forward: share of the step program's device time spent in ops
under the ``ssm`` scope (every Mamba mixer's norm-to-output-projection:
projections, convolution, the dt/B/C norms and the selective scan)."""


def read(r):
    from attribution import scope_share, step_program
    return scope_share(r, "ssm", step_program(r))
