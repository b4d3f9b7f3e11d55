"""Federated analytics (the reference fa/ examples): heavy-hitter discovery
with TrieHH + a k-percentile over the federation, no model training at all.

Run:  python examples/federated_analytics.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from fedml_tpu.fa import FASimulator, run_fa_cross_silo

# heavy hitters: which words are common across clients, with DP
clients = [["sunshine"] * 120 + ["moonlight"] * 100 + ["rare_word"]
           for _ in range(10)]
hh = FASimulator("triehh", clients, num_rounds=12, epsilon=8.0).run()
print("heavy hitters:", hh)

# k-percentile over numeric data, cross-silo over the comm layer
rs = np.random.RandomState(0)
data = [rs.lognormal(3.0, 1.0, 500) for _ in range(5)]
server = run_fa_cross_silo("k_percentile", data, k=95.0, lo=0, hi=500,
                           bins=8192)
print("federated p95:", round(server.result, 2),
      "| centralized p95:", round(float(np.percentile(
          np.concatenate(data), 95)), 2))
