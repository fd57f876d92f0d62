# Engine internals (make_sim_round, make_chunk_runner, init_carry,
# eval_rounds, make_sweep_runner) stay importable from repro.fl.engine but
# are not part of the package surface — the carry/chunk layout is free to
# change without breaking the public API.
from repro.fl.client_shard import make_schedule_runner
from repro.fl.engine import SimConfig, run_simulation_scan, run_sweep
from repro.fl.grid import GridSpec, run_grid
from repro.fl.population import PopulationConfig
from repro.fl.round import (delta_aggregate, fl_round, local_sgd,
                            make_fl_train_step, make_sharded_round_update,
                            make_train_step, weighted_aggregate)
from repro.fl.simulation import (match_uniform_m, run_simulation,
                                 run_simulation_loop, time_to_accuracy)
from repro.fl.tournament import run_tournament

__all__ = ["fl_round", "local_sgd", "make_fl_train_step", "make_train_step",
           "weighted_aggregate", "delta_aggregate",
           "make_sharded_round_update", "make_schedule_runner",
           "SimConfig",
           "GridSpec", "run_grid",
           "PopulationConfig", "run_tournament",
           "run_simulation", "run_simulation_loop", "run_simulation_scan",
           "run_sweep", "match_uniform_m", "time_to_accuracy"]
