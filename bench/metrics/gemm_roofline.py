"""Share of the roofline that the model's projection GEMMs reach.

For each device operation in the trace that is a GEMM against a
projection weight, whatever implements it (a Pallas kernel or an XLA dot):
the least time of its logical (M, N, K), max(2MNK / peak FLOP/s, HBM
bytes / peak bytes/s), over its device time, summed over the operations.
An operation is such a GEMM when it takes exactly two rank-2 operands
(M', K') and (K', N') that cover a projection weight (K, N) of the model,
padded by less than the weight's own size.  M is the rows of the engine
span the operation ran in: the prompt's length in ``engine.prefill``, the
slot count in ``engine.tick``.  The HBM bytes are those of A, B and C that
the operation's own layouts place in HBM: an array that the compiler
staged in the core's VMEM (``S(1)``) moves through HBM in the operations
that fill or drain it, not in this one.  Both sums run
over the same operations, so a GEMM that no operation can be matched to
drops out of both."""

from bench.devtrace import HBM
from bench.flops import gemm_bytes, gemm_flops, least_time_s
from bench.observe import SpanIndex

KINDS = ("custom-call", "fusion", "convolution", "dot")


def match_weight(shape, k_pad, n_pad):
    """The projection (N, K) a padded (K', N') weight operand holds."""
    best = None
    for _, n, k in shape.projections():
        if k <= k_pad < 2 * k and n <= n_pad < 2 * n:
            waste = (k_pad - k) + (n_pad - n)
            if best is None or waste < best[0]:
                best = (waste, n, k)
    return None if best is None else best[1:]


def read(obs):
    trace = obs.trace
    if trace is None:
        return None
    spans = SpanIndex([s for s in obs.spans if obs.gemm_rows(s) is not None])
    least = busy = 0.0
    for op in trace.ops:
        if op.chip != 0 or op.kind not in KINDS:
            continue
        args = op.operands()
        if len(args) != 2 or any(len(x.dims) != 2 for x in args):
            continue
        a, b = args
        (m_pad, k_pad), (k2, n_pad) = a.dims, b.dims
        if k_pad != k2:
            continue
        nk = match_weight(obs.spec.shape, k_pad, n_pad)
        span = spans.around(op.t0, op.t1)
        if nk is None or span is None:
            continue
        m = obs.gemm_rows(span)
        if m > m_pad:
            continue
        n, k = nk
        in_hbm = (a.space == HBM, b.space == HBM,
                  any(c.space == HBM for c in op.results()))
        least += least_time_s(gemm_flops(m, n, k),
                              gemm_bytes(m, n, k, in_hbm=in_hbm), obs.peak)
        busy += op.dur
    if busy <= 0:
        return None
    return 100.0 * least / busy
