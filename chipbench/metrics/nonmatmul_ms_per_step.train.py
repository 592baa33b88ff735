"""Device time per training step spent outside matmul operations: the GEMM
backend's rounding passes, norms, softmax, the optimizer. Self time of the
trace's non-matmul operations over the traced window, per step."""

from chipbench import reduce


def read(run):
    t = run.layer.get("trace")
    if not t or not run.layer.get("steps"):
        return None
    _, other = reduce.matmul_seconds(t)
    return 1e3 * other / run.layer["steps"]
