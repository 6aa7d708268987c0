from repro.train.loop import (InjectedFailure, Trainer, TrainConfig,
                              make_train_step)
from repro.train.dvfs_controller import DVFSController, SimulatedActuator

__all__ = ["Trainer", "TrainConfig", "make_train_step", "InjectedFailure",
           "DVFSController", "SimulatedActuator"]
