"""The benchmark's harness: everything shared by cells (see run.py)."""
