"""A text edgelist, one ``u v`` (or ``u v w``) line per edge, padded
with empty lines to the configuration's ``text_bytes``; opened with the
configuration's ``num_vertices`` where the traffic asks for it."""
import os

from bench.gen import writers
from bench.inputs import Files


def write(graph, config, traffic, workdir) -> Files:
    path = os.path.join(workdir, "graph.el")
    size = writers.write_text(path, graph.src, graph.dst, graph.weights,
                              base=config["id_base"],
                              pad_to=config.get("text_bytes"))
    kw = {"weighted": True} if graph.weights is not None else {}
    if traffic.get("num_vertices_hint"):
        kw["num_vertices"] = graph.num_vertices
    return Files([path], size, kw)
