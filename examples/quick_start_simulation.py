"""Quick start: config-driven FL simulation (the reference "parrot" example,
python/examples/federate/quick_start/parrot/).

Run:  python examples/quick_start_simulation.py [path/to/fedml_config.yaml]

Reference fedml_config.yaml files load unchanged. Without an argument this
uses an inline config (synthetic fallback data when no dataset files exist).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import fedml_tpu

if len(sys.argv) > 1:
    cfg = fedml_tpu.init(config_path=sys.argv[1])
else:
    cfg = fedml_tpu.init(config={
        "data_args": {"dataset": "mnist"},
        "model_args": {"model": "lr"},
        "train_args": {
            "federated_optimizer": "FedAvg",
            "client_num_in_total": 10,
            "client_num_per_round": 10,
            "comm_round": 10,
            "epochs": 1,
            "batch_size": 10,
            "learning_rate": 0.03,
        },
    })

history = fedml_tpu.run_simulation(cfg)
print("final round:", history[-1])
