"""GenZ's prediction for a cell, priced for one v5e chip (or a v5e host at
the cell's tensor-parallel degree): the paper's analytical model beside
the measurement.  The batch is the cell's slots, the lengths the mix's
mean prompt and output; printed on an earlier line of each run, never a
metric."""

from __future__ import annotations

from .model import model_spec
from .traffic import mean_length


def predict(cell: dict) -> dict:
    from repro.core.stages import Workload
    from repro.scenario import Scenario, run
    cfg, mix = cell["config"], cell["mix"]
    tp = int(cfg.get("serving", {}).get("tp", 1))
    block = int(mix["block"])
    wl = Workload(batch=int(mix["engine"]["max_slots"]),
                  tau_p=round(mean_length(mix["prompt"], block)),
                  tau_d=round(mean_length(mix["output"], block)),
                  name=cell["cell"]["name"])
    sc = Scenario(model=model_spec(cfg, cell["cell"]["config"]), workload=wl,
                  platform=f"v5e-1x1x{tp}", parallelism={"tp": tp})
    rep = run([sc], max_workers=0)[0]
    return {"platform": f"v5e-1x1x{tp}", "status": rep.status,
            "batch": wl.batch, "tau_p": wl.tau_p, "tau_d": wl.tau_d,
            "ttft_ms": None if rep.ttft_s is None else 1e3 * rep.ttft_s,
            "tpot_ms": None if rep.tpot_s is None else 1e3 * rep.tpot_s}
