"""Kernels: the selective scan's share of its HBM roofline.  The least time
the chip could take for the bytes every Mamba layer's scan must move
(``bench/flops/<family>.py`` ``scan_bytes``: x, dt and y at float32, B, C
and A) at peak HBM bandwidth, over the device time of the ops under the
``ssm_scan`` scope (the Pallas scan where the program runs it, else the jnp
scan).  The scan is bound by its VPU and EUP work, not by HBM: HBM is the
bound it is read against."""

#: the scope the scan alone runs under
SCOPE = "ssm_scan"


def read(r):
    from attribution import PROGRAM, scope_share, step_program
    scan_bytes = getattr(r.flops, "scan_bytes", None)
    if scan_bytes is None or not r.step_calls:
        return None
    share = scope_share(r, SCOPE, step_program(r))
    if share is None:
        return None
    t = share / 100.0 * r.device_s(r.trace.modules,
                                   lambda n: n.startswith(PROGRAM))
    d = r.config["model"]["d_model"]
    need = sum(scan_bytes(rows, nb // d, r.config)
               for rows, nb in r.step_calls)
    return 100.0 * need / r.peaks["hbm_bytes_per_s"] / t
