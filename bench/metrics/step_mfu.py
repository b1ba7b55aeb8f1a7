"""Model forward: FLOPs the perception steps of the profiled suite need
(counted from shapes by ``bench/flops/<family>.py``), over the device time
of the jitted step program times the chip's peak bf16 FLOP/s."""

#: the jitted decode-and-forward program's name in the device trace
PROGRAM = "jit_step"


def read(r):
    t = r.device_s(r.trace.modules, lambda n: n.startswith(PROGRAM))
    if t <= 0 or not r.step_calls:
        return None
    d = r.config["model"]["d_model"]
    need = sum(r.flops.step_flops(r.config, rows, nb // d)
               for rows, nb in r.step_calls)
    return 100.0 * need / (t * r.peaks["bf16_flops_per_s"])
