"""Host: JAX's compile events (``backend_compile...``) that started in
the window.  Every program the window runs is compiled or loaded in the
warm-up call, so this reads 0."""

from bench import spans


def read(ctx):
    parsed = spans.of(ctx)
    if parsed is None:
        return None
    return sum(s.name.startswith(spans.COMPILE)
               for s in spans.in_window(parsed.host, parsed.window))
