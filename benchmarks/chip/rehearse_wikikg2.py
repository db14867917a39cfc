"""Compile rehearsal, no chip needed: the single-machine train step at
ogbl-wikikg2 size (2,500,604 entities, 535 relations) and TransE-L2 Table 3
widths (d=400, b=1024, k=256), with the fused sparse-Adagrad kernels, for a
described TPU v5e chip. Prints each program's ``memory_analysis`` and whether
the entity table and its Adagrad accumulator are copied around the in-place
update: as the program builds the step (no donation), with the state
donated, and with the state donated and the entity count rounded up to a
multiple of the kernel's 8-row tile (2,500,608).

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 benchmarks/chip/rehearse_wikikg2.py
"""

import functools
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main():
    import jax

    from repro.kernels.sparse_adagrad import ops
    from repro.optim.sparse_adagrad import set_use_kernel

    jax.config.update("jax_enable_compilation_cache", False)
    set_use_kernel(True)
    # the backend here is the CPU, where the kernels would lower in interpret
    # mode; the described chip gets their Mosaic lowering
    ops._interpret = lambda: False
    for n_entities, donate in ((2_500_604, ()), (2_500_604, (0,)), (2_500_608, (0,))):
        label = f"{n_entities:,} entities, {'state donated' if donate else 'as built'}"
        rehearse(n_entities, donate, label)


def rehearse(n_entities, donate, label):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.common.config import KGEConfig
    from repro.core import kge_model

    cfg = KGEConfig(name="wikikg2", model="transe_l2", n_entities=n_entities,
                    n_relations=535, dim=400, gamma=19.9, lr=0.25, loss="self_adv",
                    batch_size=1024, neg_sample_size=256, neg_deg_ratio=0.0)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    shaped = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)  # noqa: E731
    state = jax.tree.map(shaped, jax.eval_shape(
        functools.partial(kge_model.init_state, cfg, overlap=cfg.overlap_update),
        jax.random.key(0)))
    b, k = cfg.batch_size, cfg.neg_sample_size
    batch = {n: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)
             for n, s in (("h", (b,)), ("r", (b,)), ("t", (b,)), ("neg", (2, 1, k)))}
    table_bytes = cfg.n_entities * cfg.dim * 4
    print(f"entity table {table_bytes / 1e9:.3f} GB, with ent_gsq "
          f"{2 * table_bytes / 1e9:.3f} GB")
    step = functools.partial(kge_model.train_step, cfg)
    try:
        compiled = jax.jit(step, donate_argnums=donate).lower(state, batch).compile()
    except Exception as e:  # the chip's compiler refusing the program is the finding
        msg = str(e)
        print(f"{label}: compile refused: {type(e).__name__}: {msg[:msg.find('  2. Size')]}")
        return
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    shape = rf"f32\[{-(-cfg.n_entities // 8) * 8},{cfg.dim}\]"
    copies = len(re.findall(rf"= {shape}[^\n]*? (?:copy|pad)\(", text))
    print(f"{label}: argument {ma.argument_size_in_bytes / 1e9:.3f} GB, output "
          f"{ma.output_size_in_bytes / 1e9:.3f} GB, alias {ma.alias_size_in_bytes / 1e9:.3f}"
          f" GB, temp {ma.temp_size_in_bytes / 1e9:.3f} GB; table-sized pads and copies "
          f"in the HLO: {copies}")
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    print(f"{label}: arguments + outputs - aliased + temp = {total / 1e9:.3f} GB "
          f"of the chip's 16 GB")


if __name__ == "__main__":
    sys.exit(main())
