"""The fixture family's reference: the program's llama module reads only
the llama leaves of the two-stack tree, so the llama family's plain
float32 reference is this one's too."""
from benchmarks.reference.llama_family import logits_at, served_gaps  # noqa: F401
