"""Host event loop: full (generation 2) collections of the interpreter
inside the window, by the program's own count
(`runtime_gc_collections_total{generation="2"}`)."""


def read(ctx):
    for key, n in ctx["window"].children(
            "runtime_gc_collections_total").items():
        if 'generation="2"' in key:
            return n
    return None
