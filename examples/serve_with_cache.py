"""End-to-end serving driver (assignment deliverable b): batched requests
through the full stack — GenerativeCache front, continuous-batching engine
over a real JAX model behind.

Run:  PYTHONPATH=src python examples/serve_with_cache.py --smoke [--arch qwen1.5-0.5b]
(without --smoke the arch serves at its published widths: a chip's job)
"""
from repro.launch.serve import main

if __name__ == "__main__":
    main()
