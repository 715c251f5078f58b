"""Share of the window spent building RT-cache rows (``rt.build`` spans:
row hashing and the instruction-encoder pass), in percent."""


def read(r):
    return 100.0 * r.span_s("rt.build") / r.window_s
