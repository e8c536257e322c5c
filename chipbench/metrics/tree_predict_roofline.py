"""Share (%) of the ``tree_predict`` kernel's roofline in the traced
window: the time its calls need at the chip's peaks (``peaks.json``) over
the device seconds its ops took.

The calls are those of the plans whose ``plan.build`` attributes
(``run.plans``) say they score a tree head nonfused with the fact program
on the kernel (``fact_backend`` ``pallas``), one call per answer in the
window.  From the shapes the attributes give (n fact rows, k features, p
nodes, l leaves), a call reads the (n, k) joined features, the (k, p)
selector, the p thresholds, the (p, l) paths and the l path counts, and
writes the (n, l) one-hot leaves, all float32; it does 2nkp + 2npl
operations.  It needs the larger of its bytes over the HBM bandwidth and
its operations over the bf16 peak.  None where the kernel did not run in
the window, or the program reports no such plan."""
KERNEL = "tree_predict"


def _needed_s(a, peaks) -> float:
    n, k, p, l = (int(a[x]) for x in ("fact_rows", "k", "p", "l"))
    nbytes = 4 * (n * k + k * p + p + p * l + l + n * l)
    ops = 2 * n * k * p + 2 * n * p * l
    return max(nbytes / peaks["hbm_bytes_per_s"],
               ops / peaks["bf16_flops_per_s"])


def read(run):
    if run.summary is None:
        return None
    secs = run.summary.kernel_s(KERNEL)
    need = sum(run.completed.get(name, 0) * _needed_s(a, run.peaks)
               for name, a in (getattr(run, "plans", None) or {}).items()
               if a.get("head") == "tree" and a.get("backend") == "nonfused"
               and a.get("fact_backend") == "pallas")
    if not secs or not need:
        return None
    return 100.0 * need / secs
