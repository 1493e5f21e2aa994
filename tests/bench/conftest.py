"""Helpers for the benchmark's CPU tests: the repo root on the path, and
cells cut to a size the CPU runs in seconds."""
import copy
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: The published sizes cut to CPU size; every other key stays the file's.
TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "head_dim": 16, "vocab_size": 256,
        "num_hidden_layers": 2}
#: A v5e's peaks; no number from a CPU run is reported under them.
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def tiny_config(name: str) -> dict:
    """A configuration file's model at CPU size, with the head layout
    (MHA or GQA 4:1) of the original."""
    from bench import spec
    conf = copy.deepcopy(spec.config(name))
    gqa = conf["num_attention_heads"] // conf["num_key_value_heads"]
    conf.update(TINY)
    conf["num_key_value_heads"] = TINY["num_attention_heads"] // gqa
    conf["model"]["overrides"].update(
        num_groups=TINY["num_hidden_layers"], d_model=TINY["hidden_size"],
        d_ff=TINY["intermediate_size"],
        num_heads=TINY["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=TINY["head_dim"], vocab_size=TINY["vocab_size"])
    conf["serving"].update(max_batch=4, num_blocks=64, max_seq_len=160)
    return conf


def tiny_cell(name: str, **traffic):
    """A cell of BENCHMARK.json with its configuration at CPU size and
    its mix's lengths cut to fit it."""
    from bench import spec
    cell = spec.cell(name)
    cell.config = tiny_config(cell.config["name"])
    cell.traffic = dict(cell.traffic,
                        prompt={"median": 24, "sigma": 0.8, "min": 4,
                                "max": 96},
                        output={"median": 8, "sigma": 0.5, "min": 2,
                                "max": 24}, **traffic)
    # A CPU-size window finishes fewer tokens than the chip's; the gap's
    # limit stays the cell's own.
    cell.checks = dict(cell.checks, sample_tokens=60, sample_requests=6,
                       limits=dict(cell.checks["limits"],
                                   tokens_compared_min=20))
    return cell
