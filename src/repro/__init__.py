"""repro — shifted-compression distributed training & serving system.

Reproduction of "Shifted Compression Framework: Generalizations and
Improvements" grown toward a production-scale jax system; see ROADMAP.md.
"""
