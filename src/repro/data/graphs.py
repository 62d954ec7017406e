"""Graph data: generators (RAND/RMAT), CSR utilities, icosahedral mesh.

RAND and RMAT are the paper's synthetic datasets (§6, Fig. 6): RAND picks
endpoints uniformly; RMAT follows Chakrabarti et al. [5] with the standard
(a,b,c,d) = (0.57, 0.19, 0.19, 0.05) parameters. Graphs are simplified
(self/duplicate edges removed) exactly as in the paper.

``icosahedral_mesh`` builds GraphCast's refinement-r multimesh
[arXiv:2212.12794]: recursively subdivided icosahedron with the union of
all refinement levels' edges.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np


def simplify_edges(src: np.ndarray, dst: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Remove self loops and duplicate (undirected) edges."""
    keep = src != dst
    src, dst = src[keep], dst[keep]
    a = np.minimum(src, dst)
    b = np.maximum(src, dst)
    e = np.unique(np.stack([a, b], axis=1), axis=0)
    return e[:, 0], e[:, 1]


def random_graph(n_nodes: int, n_edges: int, seed: int = 0
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """The paper's RAND dataset: uniform endpoints, then simplified."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n_nodes, n_edges, dtype=np.int64)
    dst = rng.integers(0, n_nodes, n_edges, dtype=np.int64)
    return simplify_edges(src, dst)


def rmat_graph(n_nodes: int, n_edges: int, seed: int = 0,
               a: float = 0.57, b: float = 0.19, c: float = 0.19
               ) -> Tuple[np.ndarray, np.ndarray]:
    """R-MAT generator [Chakrabarti et al. 2004], vectorized.

    Each edge picks one quadrant per scale via categorical draws; node ids
    are the accumulated bit paths. Power-law degrees, community structure —
    the paper's hard synthetic case (hub nodes stress boxing)."""
    rng = np.random.default_rng(seed)
    scale = int(np.ceil(np.log2(max(2, n_nodes))))
    p = np.asarray([a, b, c, 1.0 - a - b - c])
    src = np.zeros(n_edges, dtype=np.int64)
    dst = np.zeros(n_edges, dtype=np.int64)
    for bit in range(scale):
        q = rng.choice(4, size=n_edges, p=p)
        src = (src << 1) | (q >> 1)
        dst = (dst << 1) | (q & 1)
    src %= n_nodes
    dst %= n_nodes
    return simplify_edges(src, dst)


def graph500_edges(scale: int, edge_factor: int = 16, seed: int = 0
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Raw Graph500 Kronecker edge list (Graph500 spec §3): 2^scale
    vertices, ``edge_factor``·2^scale generated edges, initiator
    A/B/C = 0.57/0.19/0.19, vertex labels randomly permuted from
    ``seed``. Self loops and duplicates are kept, as the generator emits
    them; the edge store's ingest drops both."""
    rng = np.random.default_rng(seed)
    n, m = 1 << scale, edge_factor << scale
    a, b, c = 0.57, 0.19, 0.19
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for bit in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        src |= ii.astype(np.int64) << bit
        dst |= jj.astype(np.int64) << bit
    perm = rng.permutation(n)
    return perm[src], perm[dst]


def clustered_graph(n_clusters: int, cluster_size: int, seed: int = 0,
                    p_in: float = 0.5) -> Tuple[np.ndarray, np.ndarray]:
    """Triangle-rich planted-partition graph (tests/benchmarks oracle).

    Arboricity scales with cluster density — used for the Thm. 17
    arboricity-scaling benchmark (cliques pack α ≈ cluster_size/2)."""
    rng = np.random.default_rng(seed)
    srcs, dsts = [], []
    for ci in range(n_clusters):
        base = ci * cluster_size
        m = rng.random((cluster_size, cluster_size)) < p_in
        iu, ju = np.triu_indices(cluster_size, k=1)
        sel = m[iu, ju]
        srcs.append(base + iu[sel])
        dsts.append(base + ju[sel])
    # sparse inter-cluster chain keeps it connected
    chain = np.arange(n_clusters - 1) * cluster_size
    srcs.append(chain)
    dsts.append(chain + cluster_size)
    return simplify_edges(np.concatenate(srcs), np.concatenate(dsts))


def synthetic_features(n_nodes: int, d_feat: int, n_classes: int,
                       seed: int = 0) -> Dict[str, np.ndarray]:
    """Class-conditioned Gaussian features (GNN train smoke/examples)."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, n_classes, n_nodes)
    centers = rng.standard_normal((n_classes, d_feat)) * 2.0
    feats = centers[labels] + rng.standard_normal((n_nodes, d_feat))
    return {"node_feat": feats.astype(np.float32),
            "labels": labels.astype(np.int32)}


def make_gnn_batch(src: np.ndarray, dst: np.ndarray, n_nodes: int,
                   d_feat: int, n_classes: int = 0, d_target: int = 0,
                   pad_to: int = 0, seed: int = 0,
                   pos: Optional[np.ndarray] = None) -> Dict[str, np.ndarray]:
    """Fixed-shape padded GNN batch matching configs.base.gnn_input_specs."""
    n, e = n_nodes, len(src)
    n_pad = max(n, pad_to) if pad_to else n
    e_pad = max(e, pad_to) if pad_to else e
    if pad_to:
        n_pad = ((n + pad_to - 1) // pad_to) * pad_to
        e_pad = ((e + pad_to - 1) // pad_to) * pad_to
    rng = np.random.default_rng(seed)
    batch = {
        "node_feat": np.zeros((n_pad, d_feat), np.float32),
        "edge_src": np.zeros((e_pad,), np.int32),
        "edge_dst": np.zeros((e_pad,), np.int32),
        "edge_mask": np.zeros((e_pad,), np.float32),
        "node_mask": np.zeros((n_pad,), np.float32),
    }
    feats = synthetic_features(n, d_feat, max(2, n_classes), seed)
    batch["node_feat"][:n] = feats["node_feat"]
    batch["edge_src"][:e] = src
    batch["edge_dst"][:e] = dst
    batch["edge_mask"][:e] = 1.0
    batch["node_mask"][:n] = 1.0
    if d_target:
        batch["targets"] = np.zeros((n_pad, d_target), np.float32)
        batch["targets"][:n] = rng.standard_normal((n, d_target))
        if pos is None:
            pos = rng.standard_normal((n, 3)).astype(np.float32)
        batch["pos"] = np.zeros((n_pad, 3), np.float32)
        batch["pos"][:n] = pos
        batch["graph_id"] = np.zeros((n_pad,), np.int32)
    else:
        batch["labels"] = np.zeros((n_pad,), np.int32)
        batch["labels"][:n] = feats["labels"] % n_classes
        batch["label_mask"] = batch["node_mask"].copy()
    return batch


# ---------------------------------------------------------------------------
# GraphCast icosahedral multimesh
# ---------------------------------------------------------------------------

def icosahedral_mesh(refinement: int = 2):
    """Vertices + multimesh edges of a recursively refined icosahedron.

    Returns (verts (V,3) float32 unit sphere, src, dst) where the edge set
    is the union over refinement levels 0..r (GraphCast's multimesh).
    refinement=6 gives 40,962 nodes (the arch card's mesh size)."""
    phi = (1 + np.sqrt(5)) / 2
    verts = np.asarray([
        [-1, phi, 0], [1, phi, 0], [-1, -phi, 0], [1, -phi, 0],
        [0, -1, phi], [0, 1, phi], [0, -1, -phi], [0, 1, -phi],
        [phi, 0, -1], [phi, 0, 1], [-phi, 0, -1], [-phi, 0, 1]],
        dtype=np.float64)
    verts /= np.linalg.norm(verts, axis=1, keepdims=True)
    faces = np.asarray([
        [0, 11, 5], [0, 5, 1], [0, 1, 7], [0, 7, 10], [0, 10, 11],
        [1, 5, 9], [5, 11, 4], [11, 10, 2], [10, 7, 6], [7, 1, 8],
        [3, 9, 4], [3, 4, 2], [3, 2, 6], [3, 6, 8], [3, 8, 9],
        [4, 9, 5], [2, 4, 11], [6, 2, 10], [8, 6, 7], [9, 8, 1]])

    all_edges = []

    def face_edges(fs):
        e = np.concatenate([fs[:, [0, 1]], fs[:, [1, 2]], fs[:, [2, 0]]])
        a = np.minimum(e[:, 0], e[:, 1])
        b = np.maximum(e[:, 0], e[:, 1])
        return np.unique(np.stack([a, b], 1), axis=0)

    all_edges.append(face_edges(faces))
    for _ in range(refinement):
        verts_list = [verts]
        midpoint = {}
        nv = len(verts)

        def mid(i, j):
            nonlocal nv
            key = (min(i, j), max(i, j))
            if key not in midpoint:
                m = verts_list[0][i] + verts_list[0][j]
                verts_list.append((m / np.linalg.norm(m))[None])
                midpoint[key] = nv
                nv += 1
            return midpoint[key]

        verts_cat = verts
        new_faces = []
        for (i, j, k) in faces:
            # note: mid() reads verts (pre-refinement coords)
            a = mid(i, j)
            b = mid(j, k)
            c = mid(k, i)
            new_faces += [[i, a, c], [j, b, a], [k, c, b], [a, b, c]]
        verts = np.concatenate(verts_list)
        faces = np.asarray(new_faces)
        all_edges.append(face_edges(faces))

    edges = np.unique(np.concatenate(all_edges), axis=0)
    return verts.astype(np.float32), edges[:, 0].astype(np.int64), \
        edges[:, 1].astype(np.int64)
