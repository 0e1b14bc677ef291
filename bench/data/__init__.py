"""The benchmark's own repositories and detector (its data)."""
