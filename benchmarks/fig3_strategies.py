"""Paper Fig. 3: strategy ladder from reading Edgelist to building CSR.

  edgelist            read per-block Edgelists only
  degree-global       + degrees into one shared accumulator
  degree-partition4   + degrees into rho=4 partition accumulators
  csr-partition4      + staged CSR (GVEL: 4 local sorts + disjoint merge)
"""
import jax.numpy as jnp

from .common import dataset, emit, timeit


def run():
    from repro.core import degrees, build, read_edgelist_numpy
    path, v, e = dataset("web_rmat")
    el = read_edgelist_numpy(path, num_vertices=v)
    n = int(el.num_edges)
    src = jnp.asarray(el.src[:n])
    dst = jnp.asarray(el.dst[:n])

    t_read = timeit(lambda: read_edgelist_numpy(path, num_vertices=v))
    emit("fig3.edgelist", t_read, "rel=1.00x")

    def deg_global():
        degrees.degrees_global(src, v).block_until_ready()

    def deg_part():
        degrees.combine_degrees(
            degrees.degrees_partitioned(src, v, 4)).block_until_ready()

    def csr_staged():
        o, t, _ = build.csr_staged(src, dst, None, v, rho=4)
        t.block_until_ready()

    for name, extra in [("degree-global", deg_global),
                        ("degree-partition4", deg_part),
                        ("csr-partition4", csr_staged)]:
        t_extra = timeit(extra)
        total = t_read + t_extra
        emit(f"fig3.{name}", total,
             f"rel={total / t_read:.2f}x;stage_only_us={t_extra * 1e6:.1f}")


if __name__ == "__main__":
    run()
