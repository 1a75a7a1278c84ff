"""sha256 of graph_diff.fingerprint_ladder for the four older architectures (run in both trees): a ladder's, then one a program kind."""
import hashlib, json, os, sys, tempfile
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["DLT_PALLAS_INTERPRET"] = "1"
sys.path.insert(0, sys.argv[1])
from distributed_llama_tpu.analysis import graph_diff as gd
from distributed_llama_tpu.analysis.graph_audit import tiny_hybrid_header
from distributed_llama_tpu.formats.mfile import ArchType, RopeType
from distributed_llama_tpu.runtime.engine import InferenceEngine
from distributed_llama_tpu.testing import tiny_header, write_tiny_model
d = tempfile.mkdtemp()
heads = {
    "llama": tiny_header(seq_len=128),
    "qwen3": tiny_header(arch=ArchType.QWEN3, rope_type=RopeType.FALCON, seq_len=128, dim=256, hidden_dim=512, n_heads=8, n_kv_heads=4, head_dim=32),
    "qwen3_moe": tiny_header(arch=ArchType.QWEN3_MOE, rope_type=RopeType.FALCON, seq_len=128, dim=256, hidden_dim=512, moe_hidden_dim=256, n_experts=8, n_active_experts=2, n_heads=8, n_kv_heads=4, head_dim=32),
    "olmo_hybrid": tiny_hybrid_header(),
}
for name, h in heads.items():
    path = f"{d}/{name}.m"
    write_tiny_model(path, h, seed=0)
    for dtype in ("float32", "bfloat16"):
        kw = dict(speculative="off") if name == "olmo_hybrid" else {}
        eng = InferenceEngine(path, compute_dtype=dtype, batch=2, max_chunk=16, decode_chunk_size=8, kv_layout="paged", **kw)
        prints = gd.fingerprint_ladder(eng)
        doc = json.dumps({k: fp.to_dict() for k, fp in sorted(prints.items())}, sort_keys=True)
        print(name, dtype, len(prints), hashlib.sha256(doc.encode()).hexdigest()[:16], flush=True)
        for kind in sorted({k.split("[")[0] for k in prints}):
            part = json.dumps({k: fp.to_dict() for k, fp in sorted(prints.items()) if k.split("[")[0] == kind}, sort_keys=True)
            print(" ", name, dtype, kind, sum(k.split("[")[0] == kind for k in prints), hashlib.sha256(part.encode()).hexdigest()[:16], flush=True)
        eng.close()
