"""Serving example: paged-KV continuous batching over a mixed request
stream (bucketed prefill, block-table decode, page reclamation).

  PYTHONPATH=src python examples/serve_lm.py
"""
import sys

from repro.launch import serve as serve_cli


def main() -> int:
    return serve_cli.main(["--arch", "deepseek-7b", "--smoke",
                           "--requests", "10", "--slots", "4",
                           "--max-new", "12", "--page-size", "16"])


if __name__ == "__main__":
    sys.exit(main())
