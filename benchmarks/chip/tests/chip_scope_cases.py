"""A hand-made scoped trace and compiled-HLO snippet with known answers,
for the tests of ``scopes.py``."""

PAL = 'custom_call_target="tpu_custom_call"'
DEV, HOST = "/device:TPU:0", "/host:CPU"
BURN = "jit(run)/vmap(engine.burnin)"
DAY = "jit(run)/vmap()/while/body"

# Window [0, 100). A burn-in loop [0, 20) whose leaves cover [2, 16): carbon
# 4, observe 6 and the contract fit 4. The horizon loop [20, 90): power 8,
# forecast 3, problem build 2, the kernel 30, a dual update 2, a copy in
# the solve's loop 1, observe 8, ledger 2, an unscoped copy 2 and an op of
# no instruction in the map 3. The loops alone cover 15 more; idle 10.
OPS = [
    ["%while.1 = (f32[8]) while(...)", 0, 20, f"{BURN}/while"],
    ["%fusion.1 = f32[8] fusion(...)", 2, 4,
     f"{BURN}/while/body/stage.carbon/add"],
    ["%fusion.2 = f32[8] fusion(...)", 6, 6,
     f"{BURN}/while/body/stage.observe/mul"],
    ["%sort.3 = f32[8] sort(...)", 12, 4, f"{BURN}/jit(fit_pd_model)/sort"],
    ["%while.4 = (f32[8]) while(...)", 20, 70, "jit(run)/vmap()/while"],
    ["%sort.5 = f32[8] sort(...)", 20, 8, f"{DAY}/stage.power/sort"],
    ["%fusion.6 = f32[8] fusion(...)", 28, 3, f"{DAY}/stage.forecast/mul"],
    ["%fusion.7 = f32[8] fusion(...)", 31, 2,
     f"{DAY}/stage.optimize/solver.problem/mul"],
    [f"%solver.pgd_epoch.8 = f32[8] custom-call(), {PAL}", 33, 30,
     f"{DAY}/stage.optimize/while/body/solver.pgd_epoch/pallas_call"],
    ["%fusion.9 = f32[8] fusion(...)", 63, 2,
     f"{DAY}/stage.optimize/while/body/solver.dual_update/add"],
    ["%copy.10 = f32[8] copy(...)", 65, 1, f"{DAY}/stage.optimize/while/body"],
    ["%fusion.11 = f32[8] fusion(...)", 66, 8, f"{DAY}/stage.observe/min"],
    ["%fusion.12 = f32[8] fusion(...)", 74, 2, f"{DAY}/engine.ledger/add"],
    ["%copy.13 = f32[8] copy(...)", 76, 2, DAY],
    ["%fusion.14 = f32[8] fusion(...)", 80, 3, None],
]


def handmade():
    """(events, scope map) of the hand-made scoped trace."""
    events = [[DEV, "XLA Ops", n, s, d] for n, s, d, _ in OPS]
    events += [[DEV, "XLA Modules", "jit_run(1)", 0, 90],
               [HOST, "python", "bench.window", 0, 100]]
    scopes = {n.split(" = ")[0].lstrip("%"): p for n, _, _, p in OPS
              if p is not None}
    return events, scopes


# A compiled program's text: a fused computation whose root carries the
# metadata, a loop body with a fusion and a copy that carry none, and
# the entry computation.
HLO = """HloModule jit_run, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

FileNames
1 "stages.py"

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %mul.2 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(run)/vmap()/while/body/stage.power/mul" stack_frame_id=1}
}

%body.3 (p.4: f32[8]) -> f32[8] {
  %p.4 = f32[8]{0} parameter(0)
  %fusion.5 = f32[8]{0} fusion(%p.4), kind=kLoop, calls=%fused_computation.1
  %add.6 = f32[8]{0} add(%fusion.5, %p.4), metadata={op_name="jit(run)/vmap()/while/body/stage.optimize/add"}
  ROOT %copy.7 = f32[8]{0} copy(%add.6)
}

%cond.8 (p.9: f32[8]) -> pred[] {
  %p.9 = f32[8]{0} parameter(0)
  ROOT %c.10 = pred[] constant(true)
}

ENTRY %main.11 (x.12: f32[8]) -> f32[8] {
  %x.12 = f32[8]{0} parameter(0), metadata={op_name="x"}
  ROOT %while.13 = f32[8]{0} while(%x.12), condition=%cond.8, body=%body.3, metadata={op_name="jit(run)/vmap(engine.burnin)/while"}
}
"""
