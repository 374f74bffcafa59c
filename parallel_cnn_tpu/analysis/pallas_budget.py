"""Static VMEM budget verification for the Pallas kernel families.

The conv/update/tail kernels size their pipeline blocks at trace time
through the `_pick_bb` VMEM model (ops/pallas_conv.py).  A config whose
modeled footprint exceeds the Mosaic scoped-VMEM limit compiles to a
kernel that OOMs on-chip and silently falls back to XLA (resilience's
one-warning fallback) — correct numerics, quietly forfeited speed.

This verifier evaluates the model for every registered kernel
configuration at lint time *with the kernels' own code*: it installs
``pallas_conv._budget_observer`` and abstractly traces
(``jax.eval_shape`` — nothing executes, no device memory) the
registered model forwards/grads, the fused update buckets, and the
fused tail, collecting each block-size decision and its modeled bytes.
Findings:

- ``vmem-budget`` error: modeled bytes > ``_VMEM_LIMIT`` (predicted
  Mosaic OOM → silent XLA fallback at runtime).
- ``vmem-budget`` warning: modeled bytes > ``_VMEM_BUDGET`` (the
  tiling constraint forced a larger-than-wanted block; legal but worth
  eyes).
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Callable, Iterator, List, Tuple

from parallel_cnn_tpu.analysis.diagnostics import Diagnostic, Severity


@dataclass
class BudgetRecord:
    config: str       # which traced configuration produced the call
    tag: str          # kernel family tag ("conv", "update", "tail/max2"...)
    n: int            # grid extent the block divides
    bb: int           # chosen block size
    per_img: int
    w_bytes: int
    modeled: int      # modeled VMEM bytes for the chosen block


@contextlib.contextmanager
def _force_tail_kernel() -> Iterator[None]:
    """Force ``pallas_tail``'s kernel leg for the duration of an abstract
    trace so the sizing path runs on CPU too, then restore the hook."""
    from parallel_cnn_tpu.ops import pallas_tail

    prev = pallas_tail._FORCE_KERNEL
    pallas_tail._FORCE_KERNEL = True  # graftcheck: disable=global-mutation -- analyzer-internal save/force/restore of a test hook around eval_shape
    try:
        yield
    finally:
        pallas_tail._FORCE_KERNEL = prev  # graftcheck: disable=global-mutation -- analyzer-internal save/force/restore of a test hook around eval_shape


@contextlib.contextmanager
def record_budget(config: str, records: List[BudgetRecord]) -> Iterator[None]:
    from parallel_cnn_tpu.ops import pallas_conv

    prev = pallas_conv._budget_observer

    def observer(tag, n, bb, per_img, w_bytes, modeled):
        records.append(
            BudgetRecord(config, tag, n, bb, per_img, w_bytes, modeled)
        )

    pallas_conv._budget_observer = observer
    try:
        yield
    finally:
        pallas_conv._budget_observer = prev


def _registered_configs(fast: bool) -> List[Tuple[str, Callable[[List[BudgetRecord]], None]]]:
    """(name, tracer) pairs; each tracer abstractly evaluates one
    registered kernel configuration with the observer installed."""
    import jax
    import jax.numpy as jnp

    configs: List[Tuple[str, Callable]] = []

    def conv_forward(name: str, batch: int):
        def run(records: List[BudgetRecord]) -> None:
            from parallel_cnn_tpu.serve import registry

            sh = registry.get(name, conv_backend="pallas")
            params, state = jax.eval_shape(sh.init, jax.random.key(0))
            x = jax.ShapeDtypeStruct((batch, *sh.in_shape), jnp.float32)
            with record_budget(f"{name}.forward(b={batch})", records):
                jax.eval_shape(sh.forward, params, state, x)
        return run

    def conv_grad(name: str, batch: int):
        def run(records: List[BudgetRecord]) -> None:
            from parallel_cnn_tpu.nn import cifar, resnet

            model = resnet.resnet18(10, cifar_stem=True, conv_backend="pallas") \
                if name == "resnet18" else None
            assert model is not None
            params, mstate, _ = model.init(jax.random.key(0), cifar.IN_SHAPE)
            x = jax.ShapeDtypeStruct((batch, *cifar.IN_SHAPE), jnp.float32)

            def loss(p, v):
                out, _ = model.apply(p, mstate, v, train=True)
                return jnp.mean(out)

            with record_budget(f"{name}.grad(b={batch})", records):
                jax.eval_shape(jax.grad(loss), params, x)
        return run

    def update_buckets(name: str):
        def run(records: List[BudgetRecord]) -> None:
            from parallel_cnn_tpu.models import lenet_ref
            from parallel_cnn_tpu.ops import pallas_update

            params = jax.eval_shape(lenet_ref.init, jax.random.key(0))
            with record_budget(f"update.{name}", records):
                jax.eval_shape(
                    lambda p, g: pallas_update.tree_sgd(
                        p, g, lr=-0.05, scale=1.0 / 64
                    ),
                    params, params,
                )
        return run

    def tail(pool: str, shape, wshape):
        def run(records: List[BudgetRecord]) -> None:
            from parallel_cnn_tpu.ops import pallas_tail

            x = jax.ShapeDtypeStruct(shape, jnp.float32)
            w = jax.ShapeDtypeStruct(wshape, jnp.float32)
            b = jax.ShapeDtypeStruct((wshape[1],), jnp.float32)
            y = jax.ShapeDtypeStruct((shape[0],), jnp.int32)
            with _force_tail_kernel(), record_budget(f"tail.{pool}", records):
                jax.eval_shape(
                    lambda *a: pallas_tail.fused_tail_loss(*a, pool=pool),
                    x, w, b, y,
                )
        return run

    configs.append(("resnet18.forward", conv_forward("resnet18", 8)))
    configs.append(("update.lenet", update_buckets("lenet")))
    configs.append(("tail.max2", tail("max2", (64, 8, 8, 64), (1024, 10))))
    if not fast:
        configs.append(("resnet18.grad", conv_grad("resnet18", 8)))
        configs.append(("resnet34.forward", conv_forward("resnet34", 8)))
        configs.append(("resnet50.forward", conv_forward("resnet50", 8)))
        configs.append(("vgg16.forward", conv_forward("vgg16", 8)))
        configs.append(("tail.gap", tail("gap", (64, 8, 8, 64), (64, 10))))
        configs.append(("tail.none", tail("none", (64, 1024), (1024, 10))))
    return configs


def collect_budget_records(fast: bool = False) -> List[BudgetRecord]:
    records: List[BudgetRecord] = []
    for _, tracer in _registered_configs(fast):
        tracer(records)
    return records


def run_pallas_budget(fast: bool = False) -> List[Diagnostic]:
    from parallel_cnn_tpu.ops.pallas_conv import _VMEM_BUDGET, _VMEM_LIMIT

    diags: List[Diagnostic] = []
    records = collect_budget_records(fast=fast)
    if not records:
        diags.append(Diagnostic(
            rule="vmem-budget",
            severity=Severity.WARNING,
            file="<pallas>",
            line=0,
            message="no kernel block-size decisions were observed; the "
                    "budget verifier traced nothing (registry change?)",
        ))
        return diags
    for r in records:
        file = f"<pallas:{r.config}>"
        if r.modeled > _VMEM_LIMIT:
            diags.append(Diagnostic(
                rule="vmem-budget",
                severity=Severity.ERROR,
                file=file,
                line=0,
                message=f"{r.tag} block bb={r.bb}/{r.n} models "
                        f"{r.modeled / 2**20:.1f}MB VMEM, over the "
                        f"{_VMEM_LIMIT / 2**20:.0f}MB Mosaic limit — this "
                        "config OOMs on-chip and silently falls back to XLA",
            ))
        elif r.modeled > _VMEM_BUDGET:
            diags.append(Diagnostic(
                rule="vmem-budget",
                severity=Severity.WARNING,
                file=file,
                line=0,
                message=f"{r.tag} block bb={r.bb}/{r.n} models "
                        f"{r.modeled / 2**20:.1f}MB VMEM, over the "
                        f"{_VMEM_BUDGET / 2**20:.0f}MB budget (tiling forced "
                        "a larger-than-wanted block)",
            ))
    return diags
