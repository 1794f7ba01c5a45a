"""Tiny cells for the CPU tests: the same drivers, references and comparison
as the chip's cells, at sizes under `tests/data/`."""

import time

BENCH = {
    "workloads": [
        {"name": "gpt2-tiny.pretrain_tiny", "config": "gpt2-tiny",
         "traffic": "pretrain_tiny", "chips": 1},
        {"name": "llama-tiny.chat_tiny", "config": "llama-tiny",
         "traffic": "chat_tiny", "chips": 1}],
    "configs": [
        {"name": "gpt2-tiny",
         "file": "perfbench/tests/data/configs/gpt2-tiny.json"},
        {"name": "llama-tiny",
         "file": "perfbench/tests/data/configs/llama-tiny.json"}],
    "end_to_end": [
        {"name": "train_tokens_per_s", "unit": "tokens/s",
         "workloads": ["gpt2-tiny.pretrain_tiny"]},
        {"name": "serve_tokens_per_s", "unit": "tokens/s",
         "workloads": ["llama-tiny.chat_tiny"]},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [],
}


def run(workload, seed, seconds):
    """One run of a tiny cell, without the look for a chip."""
    from perfbench import run as bench_run
    return bench_run.run_cell(BENCH, workload, seed, seconds, False,
                              require_chip=False,
                              t_start=time.perf_counter())
