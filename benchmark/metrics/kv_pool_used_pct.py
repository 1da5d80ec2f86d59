"""Key/value pool: share of the full layers' pool of pages the fleet's
streams hold at the window's close (the pool's own gauge over the
configuration's pool)."""


def read(ctx):
    span = ctx["window"]
    held = span.after.get("tpu_inference.stream_kv_pages_in_use") if span else None
    pool = ctx["config"].get("model", {}).get("pool_pages")
    if not held or not pool:
        return None
    return 100.0 * held / pool
