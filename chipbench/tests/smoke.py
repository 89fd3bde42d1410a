"""Cells at the zoo's smoke sizes, for CPU tests of the harness."""
import copy

SMOKE_MODELS = {
    "nemotron-4-340b": {"n_layers": 2, "d_model": 96, "n_heads": 6,
                        "n_kv_heads": 2, "head_dim": 16, "d_ff": 384,
                        "vocab_size": 512, "rotary_dim": 16},
}


# Widest logit gap at smoke size on the CPU, over 100-127 compared tokens
# (seeds 1-8): sound runs read 0.0 to 0.0047, the fp8 control 0.033 to
# 0.052 (the int8 control 0.0027 to 0.016 does not clear the sound runs);
# over 30 tokens a stale cache read 0.37-0.51 and an altered token
# 0.70-0.82.
SMOKE_LIMIT = 0.012


def smoke_cell(workload: str, *, slots: int = 2, capacity: int = 64,
               prompt=(8, 16), output=(16, 32), rate: float = 6.0,
               limit: float = SMOKE_LIMIT, check_tokens: int = 100,
               backlog: int = 0):
    """The named cell with the zoo's ``-smoke`` model, tiny prompts and
    answers, and tiles that divide the smoke widths.  Answers are longer
    than prompts, so a decode that loses its cache writes shows.  With
    ``backlog`` the arrivals are that many requests due at the start."""
    import harness
    cell = harness.load_cell(workload)
    cfg = copy.deepcopy(cell.config)
    name = cfg["zoo"]
    cfg["zoo"] = name + "-smoke"
    cfg["zoo_overrides"] = {}
    cfg["model"].update(SMOKE_MODELS[name])
    cfg["sparsity"]["tile"] = [16, 16]
    cfg["serving"].update(sparse_block_m=16, sparse_block_n=16,
                          sparse_slice_k=16)
    cell.config = cfg
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.traffic["prompt"].update(min=prompt[0], max=prompt[1])
    cell.traffic["output"] = {"dist": "uniform", "min": output[0],
                              "max": output[1]}
    if backlog:
        cell.traffic.update(arrival="backlog", count=backlog,
                            shuffle_block=slots)
    cell.spec = copy.deepcopy(cell.spec)
    cell.spec["rate_per_s"] = rate
    cell.spec["engine"].update(slots=slots, capacity=capacity,
                               prefill_bucket=16,
                               max_prefill_batch=min(
                                   cell.spec["engine"]["max_prefill_batch"],
                                   2))
    cell.spec["check"] = {"tokens": check_tokens, "widest_logit_gap": limit}
    return cell
