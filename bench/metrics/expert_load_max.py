"""How unevenly the router loads the experts: over the sampled
``engine.prefill`` and ``engine.tick`` spans with routing counters, the
mean of the largest group's rows over an even share,
``expert_rows_max / (moe_rows / (layers with experts x experts))``.  It
reads 1.0 for perfectly even routing; the largest group bounds a grouped
GEMM's time.  Architectures without experts read nothing."""


def read(obs):
    shape = obs.spec.shape
    if not hasattr(shape, "expert_gemms"):
        return None
    groups = shape.expert_layers * shape.n_experts
    ratios = [s.attrs["expert_rows_max"] * groups / s.attrs["moe_rows"]
              for s in obs.spans if s.attrs.get("moe_rows")]
    if not ratios:
        return None
    return sum(ratios) / len(ratios)
