"""A cell cut to a size the CPU runs in seconds, for the tests."""
import copy
import time

from bench import harness

OPERANDS = "float32"
GAP_RESIDUAL = 0.012
GAP_MASS_ERR = 1e-5


def tiny(cell: str, seconds: float = 2.0):
    wl = copy.deepcopy(harness.load_json("workloads", cell + ".json"))
    cfg = copy.deepcopy(harness.load_json("configs", wl["config"] + ".json"))
    cfg["model"].update(n_topics=4, vocab_size=64)
    cfg["corpus"].update(n_docs=160, mean_doc_len=400)
    cfg["store"].update(partition_docs=16, partitions=8, max_gap_docs=24)
    cfg["check"].update(keep_every=1, queries=6)
    # On the CPU the program's VB matmuls are exact float32, so the tiny
    # cell states float32 operands (its control: bfloat16), and its
    # residual limit is set from CPU readings at this size: the program
    # 4.9e-4 to 4.3e-3 over 12 seeds, a quarter of the E-step iterations
    # 2.6e-2 and up; the mass error, the program 4e-8 to 7e-8 over 6
    # seeds, the bfloat16 control 1.6e-3 and up.
    cfg["model"]["precision"] = dict(cfg["model"].get("precision") or {},
                                     matmul_operands=OPERANDS)
    cfg["check"]["limits"].update(gap_residual=GAP_RESIDUAL,
                                  gap_mass_err=GAP_MASS_ERR)
    explore = wl["params"]["grid_docs"] < harness.load_json(
        "configs", wl["config"] + ".json")["store"]["partition_docs"]
    wl["params"].update(width_parts=[1, 4], grid_docs=4 if explore else 16)
    wl["rate_qps"] = 8.0
    wl["clients"] = 1
    return wl, cfg


def run(cell: str, monkeypatch, base=None, stand_ins=(), seed=2**33 + 9):
    """One run of the tiny cell on the CPU, past the harness's look for
    a chip, with no persistent compile cache."""
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "")
    wl, cfg = tiny(cell)
    return harness.run_cell(cell, seed, 2.0, False,
                            t_start=time.perf_counter(), workload=wl,
                            config=cfg, backend_base=base,
                            require_tpu=False, stand_ins=stand_ins)
