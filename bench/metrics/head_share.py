"""Model forward: share of the step program's device time spent in ops
under the ``head`` scope (final norm and vocabulary projection)."""


def read(r):
    from attribution import scope_share, step_program
    return scope_share(r, "head", step_program(r))
