from repro.kernels.maxsim.ops import (default_interpret,
                                      maxsim_rerank, maxsim_scores,
                                      maxsim_scores_chunked,
                                      maxsim_topk_chunked, pallas_available,
                                      quantize_int8, rerank_pallas_available)
from repro.kernels.maxsim.ref import maxsim_ref
