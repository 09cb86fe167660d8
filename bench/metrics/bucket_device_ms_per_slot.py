"""Device milliseconds per client-step slot of the bucket programs: their
device time in the traced window over the P_pad x T_pad slots (real and
padding) that the window's ``drfl.client_update`` spans report.  With
``bucket_pad_share`` it splits the bucket layer's time into padding and
the rate of one vmapped step."""
from harness import program


def read(ctx):
    slots = sum(s.counts.get("slots", 0)
                for s in program.of_layer(ctx, "client_update"))
    if not slots:
        return None
    name = program.program_name("client_update")
    seconds = ctx["tr"].module_seconds(ctx["trace"], name)
    return 1000.0 * seconds / slots if seconds > 0 else None
