"""CICS — Carbon-Intelligent Compute management (the paper's contribution).

Pipelines (paper Fig. 4): carbon fetching (carbon.py), power models
(power.py), load forecasting (forecast.py), risk-aware VCC optimization
(vcc.py), forecast ensembles + CVaR risk objective (risk.py), SLO
violation detection (slo.py), Borg-like admission under VCCs
(admission.py), and the beyond-paper spatial layer (spatial.py: greedy
pre-shift + joint spatio-temporal optimization). Every optimizer is an
assembly over the ONE generic projected-gradient layer (solver.py:
projections, smooth peak, lr scaling, dual ascent, kernel-epoch
dispatch). ``stages.py`` composes the pipelines into THE staged day cycle
(pure stage functions -> one pure day step), which ``sim.engine`` scans
over days and vmaps over a (scenario x seed) batch.
"""
from repro.core import (admission, carbon, forecast, power, risk, slo,
                        solver, spatial, stages, vcc)

__all__ = ["admission", "carbon", "forecast", "power", "risk", "slo",
           "solver", "spatial", "stages", "vcc"]
