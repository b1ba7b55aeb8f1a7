"""Model forward: share of the step program's device time spent in ops
under the ``attention_core`` scope (scores, masking, softmax and × V of
every layer, inside ``attention``: the fused kernel where the program
runs it, else the chunked jnp attention)."""


def read(r):
    from attribution import scope_share, step_program
    return scope_share(r, "attention_core", step_program(r))
