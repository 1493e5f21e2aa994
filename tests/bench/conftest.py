"""Helpers for the benchmark's CPU tests: the repo root on the path, and
cells cut to a size the CPU runs in seconds."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import spec  # noqa: E402

#: A v5e's peaks; no number from a CPU run is reported under them.
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
#: Configurations of BENCHMARK.json, in its order.
CONFIGS = [c["name"] for c in spec.benchmark()["configs"]]


def tiny_config(name: str, control: bool = False) -> dict:
    """A configuration file's model cut to CPU size by its kind's ``tiny``
    (``bench/models/<kind>.py``), and its serving settings cut to fit."""
    conf = spec.config(name)
    conf = spec.model_kind(conf).tiny(conf, control)
    conf["serving"].update(max_batch=4, num_blocks=64, max_seq_len=160)
    return conf


def tiny_cell(name: str, **traffic):
    """A cell of BENCHMARK.json with its configuration at CPU size and
    its mix's lengths cut to fit it."""
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
