"""Share of the window the host spends in the batcher's dispatch and
drain (``predict.dispatch`` + ``predict.drain`` spans: host-side batch
assembly plus waiting on the device), in percent."""


def read(r):
    return 100.0 * (r.span_s("predict.dispatch")
                    + r.span_s("predict.drain")) / r.window_s
