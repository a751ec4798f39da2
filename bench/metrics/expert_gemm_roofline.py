"""Share of the roofline that the grouped expert GEMMs reach.

For each device operation inside an ``engine.prefill`` or ``engine.tick``
span with routing counters that is a grouped expert GEMM, whatever
implements it (the Pallas grouped GEMM or XLA's ragged dot): the least
time of its logical work, max(2 rows N K / peak FLOP/s, HBM bytes / peak
bytes/s), over its device time, summed over the operations.  An operation
is such a GEMM when one operand is a stack (G, K, N) of expert weights of
the model (G a multiple of its expert count: the served path passes every
layer's experts as one stack) and another is the rows (M', K).  rows is
the span's ``moe_rows`` over its layers with experts: the token copies
one layer's GEMM multiplies.  The HBM bytes are the weights of the span's
``experts_touched`` experts, where the stack is in HBM, and A (rows, K)
and C (rows, N) where the operation's own layouts place them in HBM.
Architectures without expert GEMMs read nothing."""

from bench.devtrace import HBM
from bench.flops import BF16, least_time_s
from bench.observe import SpanIndex


def match(shape, args):
    """(N, K, weights, rows) of the expert GEMM an operation's operands
    hold, or None."""
    for w in (a for a in args if len(a.dims) == 3):
        g, k, n = w.dims
        if g % shape.n_experts or (n, k) not in {
                (n2, k2) for _, n2, k2 in shape.expert_gemms()}:
            continue
        for a in args:
            if len(a.dims) == 2 and a.dims[1] == k:
                return n, k, w, a
    return None


def read(obs):
    shape, trace = obs.spec.shape, obs.trace
    if trace is None or not hasattr(shape, "expert_gemms"):
        return None
    spans = SpanIndex([s for s in obs.spans if s.attrs.get("moe_rows")])
    least = busy = 0.0
    for op in trace.ops:
        if op.chip != 0:
            continue
        found = match(shape, op.operands())
        span = spans.around(op.t0, op.t1) if found else None
        if span is None:
            continue
        n, k, w, a = found
        rows = span.attrs["moe_rows"] / shape.expert_layers
        flops = 2.0 * rows * n * k
        nbytes = BF16 * (
            (span.attrs["experts_touched"] * k * n if w.space == HBM else 0)
            + (rows * k if a.space == HBM else 0)
            + (rows * n if any(c.space == HBM for c in op.results()) else 0))
        least += least_time_s(flops, nbytes, obs.peak)
        busy += op.dur
    if busy <= 0:
        return None
    return 100.0 * least / busy
