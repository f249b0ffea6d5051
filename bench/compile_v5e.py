"""Compile a cell's decode and prefill programs for a described TPU v5e,
without a chip, and print what the compiler's memory analysis says.

    JAX_PLATFORMS=cpu python3 bench/compile_v5e.py [--slots N] <cell> ...

Each cell's engine programs (the full-width decode step, the packed
decode at the largest bucket below it, the prefill chunk) are lowered at
the cell's sizes from shapes alone and compiled for one chip of a
``v5e:2x2`` topology, with the Pallas kernels compiled for Mosaic rather
than interpreted. A refusal here is what the chip's compiler would say.
``--slots N`` compiles at N slots instead of the cell's own count.
"""
import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(cells, slots=None) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.lib import harness, spec
    from repro.core import dispatch
    from repro.models import lm

    jax.config.update("jax_enable_compilation_cache", False)
    dispatch._interpret = lambda interpret: False      # compile for Mosaic
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    for name in cells:
        cell = spec.load_cell(name)
        eng = cell.traffic["engine"]
        sc, cfg = harness.program_config(cell.config, cell.traffic, True,
                                         cell.reduced)
        cfg = cfg.replace(loki=dataclasses.replace(cfg.loki,
                                                   backend="pallas"))
        ps, smax = sc.pool.page_size, eng["smax"]
        n = slots or eng["n_slots"]
        max_pages = -(-smax // ps)
        params = sds(jax.eval_shape(lambda k: lm.init(k, cfg),
                                    jax.random.PRNGKey(0)))
        cache = sds(jax.eval_shape(lambda: lm.init_paged_cache(
            cfg, 1 + n * max_pages, ps, jnp.float32, n_slots=n)))

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

        programs = {
            "decode_step": (lambda p, c, t, pl, pt: lm.decode_step(
                p, cfg, c, t, pl, page_table=pt, page_size=ps),
                (params, cache, i32(n), i32(n), i32(n, max_pages))),
            f"decode_step_packed[b{n // 2}]": (
                lambda p, c, t, pl, pt, si: lm.decode_step(
                    p, cfg, c, t, pl, page_table=pt, page_size=ps,
                    slot_idx=si),
                (params, cache, i32(n // 2), i32(n // 2),
                 i32(n // 2, max_pages), i32(n // 2))),
            "prefill_chunk": (lambda p, c, tk, st, nv, row, sl:
                              lm.prefill_chunk(p, cfg, c, tk, st, nv, row,
                                               ps, slot=sl),
                              (params, cache, i32(1, sc.pool.prefill_chunk),
                               i32(), i32(), i32(max_pages), i32())),
        }
        for prog, (fn, args) in programs.items():
            try:
                compiled = jax.jit(fn, donate_argnums=(1,)).lower(
                    *args).compile()
            except jax.errors.JaxRuntimeError as e:
                print(f"{name} {prog} at {n} slots: refused: "
                      f"{str(e).splitlines()[0]}", flush=True)
                continue
            ma = compiled.memory_analysis()
            print(f"{name} {prog} at {n} slots: kernel "
                  f"{'tpu_custom_call' in compiled.as_text()}, arguments "
                  f"{ma.argument_size_in_bytes}, outputs "
                  f"{ma.output_size_in_bytes}, aliased "
                  f"{ma.alias_size_in_bytes}, temporaries "
                  f"{ma.temp_size_in_bytes} bytes", flush=True)


if __name__ == "__main__":
    args = sys.argv[1:]
    n_slots = None
    if args[:1] == ["--slots"]:
        n_slots, args = int(args[1]), args[2:]
    main(args, n_slots)
