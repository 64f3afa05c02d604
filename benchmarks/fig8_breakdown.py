"""Paper Fig. 8: time split between Edgelist reading and CSR conversion."""
from .common import DATASETS, dataset, emit, timeit


def run():
    from repro.core import convert_to_csr, load_edgelist

    for ds in DATASETS:
        path, v, e = dataset(ds)
        el = load_edgelist(path, engine="numpy", num_vertices=v)
        t_el = timeit(lambda: load_edgelist(path, engine="numpy",
                                            num_vertices=v))
        t_c = timeit(lambda: convert_to_csr(el, engine="numpy"))
        emit(f"fig8.{ds}.edgelist", t_el,
             f"share={t_el / (t_el + t_c) * 100:.0f}%")
        emit(f"fig8.{ds}.to_csr", t_c,
             f"share={t_c / (t_el + t_c) * 100:.0f}%")


if __name__ == "__main__":
    run()
