"""State-space mixers, as a kernel: the least time the chip could take
for the traced steps — the input projections' weights once a step, each
real row's state-space and convolution state read and written — over the
device time of the mixers' operations (padding rows are fetched too and
not counted: the share reads what the real rows needed)."""
from benchmark.metrics import _stream_trace as st


def read(ctx):
    n = st.steps(ctx)
    rows = st.counter(ctx, "rows_one_step", "traced")
    if not st.layer_seconds(ctx, "ssm") or not n or not rows:
        return None
    model, costs = st.costs(ctx)
    return st.roofline_pct(ctx, "ssm", *costs.ssm_cost(model, rows, n))
