"""Expert layers' grouped products, as a kernel: the least time the chip
could take for them over the traced steps — the weights of the DISTINCT
held experts each layer hit (the step's own counter; pairs x bytes would
read over 100%) and the pairs' activations, or the FLOPs of the held
pairs — over the device time of the ``gmm`` kernels."""
from benchmark.metrics import _stream_trace as st


def read(ctx):
    hit = st.counter(ctx, "experts_hit", "traced")
    if not st.layer_seconds(ctx, "moe") or not hit:
        return None
    model, costs = st.costs(ctx)
    return st.roofline_pct(ctx, "moe", *costs.moe_cost(
        model, st.counter(ctx, "pairs_held", "traced"), hit))
