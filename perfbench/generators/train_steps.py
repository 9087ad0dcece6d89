"""A pre-training job: steps for the whole window, one fresh seeded host batch
a step. The loop itself runs in the chip-bound TrainWorker
(`perfbench/actors.py train_loop`); this file only turns the mix's parameters
into its plan."""

KIND = "train"


def plan(params: dict, seed: int, seconds: float, model: dict) -> dict:
    return {"rows_per_chip": params["rows_per_chip"], "seq_len": model["seq_len"],
            "trace_after_steps": params["trace_after_steps"],
            "trace_steps": params["trace_steps"]}
