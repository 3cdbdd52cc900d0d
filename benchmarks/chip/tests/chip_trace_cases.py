"""A hand-made trace with known answers, shared by the chip benchmark's
tests."""

PAL = 'custom_call_target="tpu_custom_call"'
DEV, HOST = "/device:TPU:0", "/host:CPU"


def handmade():
    """Window [0, 100); busy [10, 40) and [50, 70) with a nested while;
    two Pallas events of 10 and 5; idle gaps 10, 10 and 30 long."""
    ops = [[DEV, "XLA Ops", "%while.1 = (f32[8]) while(...)", 10, 30],
           [DEV, "XLA Ops", f"%closed_call.2 = f32[8] custom-call(), {PAL}",
            12, 10],
           [DEV, "XLA Ops", "%fusion.3 = f32[8] fusion(...)", 25, 10],
           [DEV, "XLA Ops", f"%closed_call.2 = f32[8] custom-call(), {PAL}",
            50, 5],
           [DEV, "XLA Ops", "%copy.4 = f32[8] copy(...)", 55, 15],
           [DEV, "XLA Modules", "jit_full_solve(7)", 10, 30],
           [DEV, "XLA Modules", "jit_resolve(8)", 50, 20]]
    host = [[HOST, "python", "bench.window", 0, 100],
            [HOST, "python", "bench.day", 0, 100],
            [HOST, "python", "PjitFunction(resolve)", 42, 6],
            [HOST, "python", "build_inputs", 72, 28],
            [HOST, "main/1", "Execute", 46, 6]]
    return ops + host
