"""Host milliseconds per ``OpenOpticsNet.run`` call in the window, outside
the wait for the device: (the seconds of the program's span
``OpenOpticsNet.run`` less those of ``run.device_wait``) over the calls,
read by ``bench/program_trace.py``. Nothing to read in a program without
the spans."""


def read(ctx):
    if ctx.trace is None:
        return None
    n = ctx.trace.get("span_n", {}).get("OpenOpticsNet.run")
    if not n:
        return None
    span_s = ctx.trace["span_s"]
    return 1e3 * (span_s["OpenOpticsNet.run"]
                  - span_s.get("run.device_wait", 0.0)) / n
