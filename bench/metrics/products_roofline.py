"""Share of the roofline reached by the coded head's device products: the
packed ``coded_matvec`` Pallas kernel and the generated-parity kernel.

Least time per call = max(ops / bf16 peak, bytes / HBM bandwidth) from the
call's shapes (``flops.py``; the float32 contraction at HIGHEST has no
published peak, so the compute term uses the bf16 peak), summed over the
window's calls, over those kernels' device time in the profiler trace."""
import flops

KERNELS = ("coded_matvec_pallas", "gen_parity_matvec_pallas")


def read(run):
    if run.device is None or not run.kernel_calls:
        return None
    t = sum(v for k, v in run.device["module_s"].items()
            if any(n in k for n in KERNELS))
    if t <= 0:
        return None
    least, _bound = flops.products_least_seconds(run.kernel_calls, run.peaks)
    return 100.0 * least / t
