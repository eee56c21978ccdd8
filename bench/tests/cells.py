"""The benchmark file with the cells that are kept for later PRs but not
yet proved on the chip, so the tests cover their generator and paths."""
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

DEFERRED = {
    "configs": [
        {"name": "pub1.4m-w10-x4", "source": "https://arxiv.org/abs/1010.3053",
         "file": "bench/configs/pub1.4m-w10-x4.json", "reduced": [],
         "why": "the same job as 4 reducers on 4 chips"}],
    "workloads": [
        {"name": "pub1.4m-w10-x4.zipf", "config": "pub1.4m-w10-x4",
         "traffic": "zipf", "chips": 4, "why": "4 chips"}],
}


def spec() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        out = json.load(f)
    for key, extra in DEFERRED.items():
        have = {x["name"] for x in out[key]}
        out[key] += [x for x in extra if x["name"] not in have]
    return out


SPEC = spec()
