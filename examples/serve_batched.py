"""Batched serving example: decode a few requests against a reduced model
with the KV-cache/SSM-state decode path (the one dryrun.py proves at
32k/524k context on the production mesh).

    PYTHONPATH=src python examples/serve_batched.py --arch mamba2-780m
"""
import argparse

import sys

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import main as serve_main


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    args, rest = ap.parse_known_args()
    enable_compile_cache()
    sys.argv = ["serve", "--arch", args.arch, "--requests", "4",
                "--slots", "2", "--prompt-len", "6", "--gen-len", "8"] + rest
    serve_main()


if __name__ == "__main__":
    main()
