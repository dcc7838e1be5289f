"""``python -m roc_tpu.export`` — thin entry point for the serve
export CLI (the implementation lives in ``roc_tpu/serve/export.py``,
same packaging convention as ``roc_tpu.timeline``)."""

from .serve.export import main  # noqa: F401

if __name__ == "__main__":
    import sys
    sys.exit(main())
