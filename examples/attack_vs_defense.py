"""Attack vs defense: byzantine clients against a robust aggregator, all as
round-program plugins (reference: core/security/fedml_attacker.py /
fedml_defender.py wired by security_args; here the same config keys compose
transforms into the jitted round — simulation/simulator.py).

Run:  python examples/attack_vs_defense.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import fedml_tpu
from fedml_tpu.simulation.simulator import Simulator


def run(defense: bool) -> float:
    cfg = fedml_tpu.init(config={
        "data_args": {"dataset": "digits", "partition_method": "hetero",
                      "partition_alpha": 0.5},
        "model_args": {"model": "mlp"},
        "train_args": {"federated_optimizer": "FedAvg",
                       "client_num_in_total": 8, "client_num_per_round": 8,
                       "comm_round": 10, "epochs": 1, "batch_size": 32,
                       "learning_rate": 0.1},
        "validation_args": {"frequency_of_the_test": 0},
        "comm_args": {"backend": "sp"},
        "security_args": {
            "enable_attack": True, "attack_type": "byzantine",
            "attack_spec": {"byzantine_client_num": 2,
                            "attack_mode": "random"},
            **({"enable_defense": True, "defense_type": "multikrum",
                "defense_spec": {"byzantine_client_num": 2}} if defense
               else {}),
        },
    })
    sim = Simulator(cfg)
    sim.run(10)
    return sim.evaluate()["test_acc"]


acc_defended = run(defense=True)
acc_undefended = run(defense=False)
print(f"under byzantine attack: defended acc={acc_defended:.3f}  "
      f"undefended acc={acc_undefended:.3f}")
assert acc_defended > acc_undefended - 0.02, (
    "multikrum should not be worse than no defense under attack")
print("defense held against byzantine clients")
