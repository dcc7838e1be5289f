"""Model zoo.  :func:`model_builders` is THE name → builder registry —
the training CLI (``train/cli.py``), the serve export CLI
(``serve/export.py``), and the benchmarks all resolve ``--model``
through it, so the vocabularies can never diverge."""

from __future__ import annotations

from typing import Callable, Dict


def model_builders() -> Dict[str, Callable]:
    """Lazily imported so ``import roc_tpu.models`` stays jax-light."""
    from .appnp import build_appnp
    from .deepergcn import build_deepergcn
    from .gat import build_gat
    from .gcn import build_gcn
    from .gcn2 import build_gcn2
    from .gtrans import build_gtrans
    from .gin import build_gin
    from .rgcn import build_rgcn
    from .sage import build_sage
    from .sgc import build_sgc
    return {"gcn": build_gcn, "sage": build_sage, "gin": build_gin,
            "gat": build_gat, "sgc": build_sgc, "appnp": build_appnp,
            "gcn2": build_gcn2, "rgcn": build_rgcn,
            "deepergcn": build_deepergcn, "gtrans": build_gtrans}
