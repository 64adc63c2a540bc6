"""A cell small enough for the CPU: the shapes of the real cells, scaled
down (300 corpus series of length 32, support learned on 8)."""

CONFIG = {"n_train": 300, "n_test": 64, "T": 32, "d": 1,
          "support": {"n_series": 8, "theta": 1.0},
          "serving": {"seed_k": 2, "prefix_frac": 0.5}}
OPEN = {"loop": "open", "queries": "retrieval", "batch": 16,
        "rate_qps": 40.0}
OFFLINE = {"loop": "offline", "queries": "classify", "batch": 16}


def cell(traffic, shards=1):
    return {"name": "tiny", "config": CONFIG, "traffic": traffic,
            "chips": shards, "shards": shards}
