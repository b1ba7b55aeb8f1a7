"""Model forward: share of the step program's device time spent in ops
under the ``attention`` scope (norm, projections, qk-norm, RoPE, scores
and output projection of every layer)."""


def read(r):
    from attribution import scope_share, step_program
    return scope_share(r, "attention", step_program(r))
