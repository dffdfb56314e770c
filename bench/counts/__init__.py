"""Operation counts (FLOPs and bytes) computed from shapes.

Each function counts the work the *operation* needs, not what one
implementation of it happens to do: no recomputation, no re-reads of a
tile, no padding beyond what the operation is defined over.  A later
kernel for the same operation is judged against the same count.
"""
