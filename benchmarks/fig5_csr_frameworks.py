"""Paper Fig. 5/6: CSR reading (Edgelist read + CSR convert) vs frameworks.

  hornet/gunrock analogue -> naive stream read + python CSR insert
  pigo                    -> two-pass read + single-stage global CSR
  gvel                    -> single-pass read + staged rho=4 CSR

Across the three Table-1 graph classes (web / social / road stand-ins).
"""
from .common import DATASETS, dataset, emit, timeit


def run():
    from repro.core import baselines, convert_to_csr, read_edgelist_numpy

    for ds in DATASETS:
        path, v, e = dataset(ds)

        def naive():
            el = baselines.read_edgelist_naive(path, num_vertices=v)
            baselines.csr_pigo(el)

        def pigo():
            el = baselines.read_edgelist_pigo(path, num_vertices=v)
            baselines.csr_pigo(el)

        def gvel():
            el = read_edgelist_numpy(path, num_vertices=v)
            convert_to_csr(el, engine="numpy")

        t_n = timeit(naive, repeat=1, warmup=0)
        t_p = timeit(pigo)
        t_g = timeit(gvel)
        emit(f"fig5.{ds}.naive_framework", t_n, f"edges_per_s={e / t_n:.3e}")
        emit(f"fig5.{ds}.pigo", t_p,
             f"edges_per_s={e / t_p:.3e};vs_naive={t_n / t_p:.1f}x")
        emit(f"fig5.{ds}.gvel", t_g,
             f"edges_per_s={e / t_g:.3e};vs_naive={t_n / t_g:.1f}x;"
             f"vs_pigo={t_p / t_g:.2f}x")


if __name__ == "__main__":
    run()
