"""Device: share of the profiled suite in which no operation runs on the
device and no program span is open but containers (the suite root, the
scheduler's ``sched.task`` and the executor's ``task.run``): idle time
that no span of the program explains."""


def read(r):
    from attribution import idle_unattributed
    return idle_unattributed(r)
