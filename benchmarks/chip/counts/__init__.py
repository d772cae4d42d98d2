"""Operations and bytes each measured kernel needs, from its shapes."""
