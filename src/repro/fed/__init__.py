from repro.fed.client import make_local_trainer
from repro.fed.engine import (ClientUpdateSpec, MeshSimScan, SimScan,
                              compress_merge_leaf, init_mesh_residuals,
                              make_mesh_sim_scan, make_sim_scan, spec_for)
from repro.fed.mesh_round import (make_fl_round_step, make_mesh_round_step,
                                  make_round_body)
from repro.fed.round_step import (FusedRoundStep, make_masked_local_trainer,
                                  make_round_step)
from repro.fed.server import FLServer
from repro.fed.simulation import (FLSimConfig, FLSimResult, mlp_accuracy,
                                  mlp_init, mlp_loss, plan_cohort, run_fl,
                                  run_fl_traced)

__all__ = ["make_local_trainer", "FLServer", "make_fl_round_step",
           "make_mesh_round_step", "make_round_body",
           "make_round_step", "make_masked_local_trainer", "FusedRoundStep",
           "ClientUpdateSpec", "spec_for", "compress_merge_leaf",
           "make_sim_scan", "SimScan",
           "make_mesh_sim_scan", "MeshSimScan", "init_mesh_residuals",
           "FLSimConfig", "FLSimResult", "run_fl", "run_fl_traced",
           "plan_cohort", "mlp_init", "mlp_loss", "mlp_accuracy"]
