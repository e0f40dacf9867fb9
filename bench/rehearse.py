"""Compile a cell's largest programs for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python bench/rehearse.py --workload nemo-lora-train

Prints ``memory_analysis()`` of the serving step at its widest signature
(or of the training step) for one chip of a described ``v5e:2x2``, which is
how each configuration file's ``rehearsal`` entry was made. Nothing runs.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def _analysis(compiled) -> dict:
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    return {k: int(getattr(m, k)) for k in keys}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import program
    from bench.spec import load_cell

    cell = load_cell(args.workload)
    c, t = cell.config, cell.traffic
    ref = cell.reference()
    cfg = program.model_config(c, ref)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        """Shapes (of arrays or of shapes) placed on the described chip."""
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=dev), tree)

    wshape = jax.eval_shape(lambda k: ref.make_weights(c, k),
                            jax.random.PRNGKey(0))
    weights = on_chip(wshape)
    t0 = time.perf_counter()
    if cell.kind == "serve":
        from repro.serve.api import make_engine
        e = t["engine"]
        adapters = [ref.make_adapter(c, jax.random.PRNGKey(i))
                    for i in range(c["lora"]["adapters"])]
        eng = make_engine(cfg, wshape, adapters, mode="paged",
                          max_slots=e["max_slots"], max_len=e["max_len"],
                          page_size=e["page_size"],
                          num_pages=c["serve"]["num_pages"],
                          prefill_chunk=e["prefill_chunk"])
        B, C = e["max_slots"], eng.chunk_buckets[-1]
        nb = eng.block_buckets[-1]

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)
        lowered = jax.jit(eng._step_fn, donate_argnums=(2,)).lower(
            weights, on_chip(eng.adapters), on_chip(eng.cache),
            sds((B, C), jnp.int32), sds((B,), jnp.int32),
            sds((B,), jnp.int32), sds((B, nb), jnp.int32),
            sds((B,), jnp.int32), sds((2,), jnp.uint32),
            sds((B,), jnp.float32))
        what = f"serve step C={C} nb={nb} pages={eng.layout.num_pages}"
        kv = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                 for x in jax.tree.leaves(eng.cache))
        print(f"kv pool bytes {kv}")
    else:
        from repro.models.transformer import ExecConfig
        from repro.optim import adamw
        from repro.train.steps import TrainHParams, make_train_step
        rows = t["tokens_per_step"] // t["seq_len"]
        hp = TrainHParams(microbatches=rows // t["microbatch_rows"],
                          adamw=adamw.AdamWConfig(**t["adamw"]))
        step = jax.jit(make_train_step(cfg, ExecConfig(remat=t["remat"]), hp),
                       donate_argnums=(1, 2))
        lora = jax.eval_shape(lambda k: ref.make_adapter(c, k),
                              jax.random.PRNGKey(0))
        opt = jax.eval_shape(adamw.init, lora)
        batch = {"tokens": jax.ShapeDtypeStruct((rows, t["seq_len"]),
                                                jnp.int32),
                 "labels": jax.ShapeDtypeStruct((rows, t["seq_len"]),
                                                jnp.int32)}
        lowered = step.lower(weights, on_chip(lora), on_chip(opt),
                             on_chip(batch),
                             on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32)))
        what = (f"train step {rows} x {t['seq_len']} as "
                f"{hp.microbatches} microbatches")
    compiled = lowered.compile()
    print(f"{cell.name}: {what}: compiled in "
          f"{time.perf_counter() - t0:.1f}s; memory_analysis "
          f"{_analysis(compiled)}")


if __name__ == "__main__":
    main()
